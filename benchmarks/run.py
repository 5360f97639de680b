"""Benchmark driver: one module per paper table/figure (+ kernels).

Prints ``name,us_per_call,derived`` CSV. Roofline terms for the 40
(arch x shape) cells come from the dry-run (launch/dryrun.py --all); this
harness covers the paper-side experiments and kernels, which run at full
fidelity on CPU.

``--smoke`` shrinks every module that supports it to CI-sized problems;
``--json PATH`` additionally writes the records as JSON (the CI benchmark
job uploads that file as an artifact).
"""
from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import sys


def _modules():
    """Benchmark modules, importable both via -m and as a plain script."""
    try:
        from . import (batched_sweep, coded_moe_dispatch, delta_sweep,
                       fig5_load_curve, fused_sweep, hierarchy_sweep,
                       kernel_bench, pagerank_phases, recovery_bench,
                       scale_sweep, straggler_bench, table2_snap,
                       theorem_tradeoffs)
    except ImportError:
        root = pathlib.Path(__file__).resolve().parents[1]
        sys.path[:0] = [str(root), str(root / "src")]
        from benchmarks import (batched_sweep, coded_moe_dispatch,
                                delta_sweep, fig5_load_curve, fused_sweep,
                                hierarchy_sweep, kernel_bench,
                                pagerank_phases, recovery_bench,
                                scale_sweep, straggler_bench, table2_snap,
                                theorem_tradeoffs)
    return (fig5_load_curve, theorem_tradeoffs, pagerank_phases, scale_sweep,
            batched_sweep, fused_sweep, kernel_bench, coded_moe_dispatch,
            straggler_bench, table2_snap, recovery_bench, delta_sweep,
            hierarchy_sweep)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem sizes (CI benchmark gate)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write records as JSON to PATH")
    args = ap.parse_args(argv)
    mods = _modules()              # puts src/ on the path in script mode
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    records: list[dict] = []
    if args.json:                  # fail fast on an unwritable path
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke, "records": records}, f)

    print("name,us_per_call,derived")

    def report(name: str, us: float, derived: str = ""):
        print(f"{name},{us:.1f},{derived}", flush=True)
        records.append({"name": name, "us_per_call": us, "derived": derived})

    for mod in mods:
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        try:
            mod.run(report, **kwargs)
        except Exception as e:  # noqa: BLE001
            report(mod.__name__.split(".")[-1] + "_FAILED", -1.0,
                   f"{type(e).__name__}: {e}")
            raise
        finally:
            if args.json:
                with open(args.json, "w") as f:
                    json.dump({"smoke": args.smoke, "records": records}, f,
                              indent=2)


if __name__ == "__main__":
    main()
