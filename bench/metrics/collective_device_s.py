"""Device seconds per iteration of the exchange's collective ops (the
all_gather, or the all-reduce the compiler lowers it to at B=1), on the
slowest chip."""
import re

OP = re.compile(r"^%(all-gather|all-reduce|collective-permute|all-to-all"
                r"|reduce-scatter)(-start|-done)?[.\s]")


def read(rec):
    tr = rec["trace"]
    if not tr:
        return None
    per_chip = [sum(s for k, s in ch["ops"].items() if OP.search(k))
                for ch in tr["chips"]]
    return max(per_chip) / rec["iters"] if any(per_chip) else None
