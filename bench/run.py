"""Run one benchmark cell and print its result as the last stdout line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's graph from --seed, sets up the program's session, warms
it up, runs iterations back to back for --seconds, checks the final state
against the plain reference, and prints one JSON object. --trace 1 profiles
the window and reports the per-layer metrics instead of the end-to-end
ones. Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = harness.run_cell(harness.load_spec(ROOT), args.workload,
                                  args.seed, args.seconds, bool(args.trace),
                                  t_start=T_START, root=ROOT)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
