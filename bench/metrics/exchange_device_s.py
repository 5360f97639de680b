"""Device seconds per iteration of the jitted exchange program (encode,
all_gather, strip/decode), on the slowest chip.

The exchange has no stable name of its own yet: it is the jitted shard_map
of `per_server`, the module `jit_per_server(<id>)` in the trace, matched by
MODULE.
"""
import re

MODULE = re.compile(r"^jit_per_server\(")


def read(rec):
    tr = rec["trace"]
    if not tr:
        return None
    per_chip = [sum(s for k, s in ch["modules"].items() if MODULE.search(k))
                for ch in tr["chips"]]
    return max(per_chip) / rec["iters"] if any(per_chip) else None
