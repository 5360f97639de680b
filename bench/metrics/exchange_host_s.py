"""Host seconds per iteration on the fused exchange's host side: the
`phase.encode` (local word building) and `phase.decode` (pull-back and
re-index) spans."""


def read(rec):
    s = [rec["spans"].get(k) for k in ("phase.encode", "phase.decode")]
    return sum(s) / rec["iters"] if all(s) else None
