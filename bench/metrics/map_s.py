"""Host seconds per iteration in the program's `phase.map` spans."""


def read(rec):
    s = rec["spans"].get("phase.map")
    return s / rec["iters"] if s else None
