"""Percent of the traced window in which no operation ran on the device,
mean over the chips."""


def read(rec):
    tr = rec["trace"]
    if not tr or not tr["chips"] or tr["window_s"] <= 0:
        return None
    busy = sum(ch["busy_s"] for ch in tr["chips"]) / len(tr["chips"])
    return 100.0 * (1.0 - busy / tr["window_s"])
