"""Host seconds per iteration in the program's `phase.reduce` spans."""


def read(rec):
    s = rec["spans"].get("phase.reduce")
    return s / rec["iters"] if s else None
