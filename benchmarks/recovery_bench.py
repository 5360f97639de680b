"""Recovery cost: coded plan repair vs the legacy uncoded fallback.

For each failure-set size m the bench kills m servers mid-run and measures
what the rest of the job pays, both ways:

  * **coded repair** (the PR 7 default): `ShufflePlan.repair` hands the dead
    senders' columns to healthy (r+1)-group members, so post-failure
    iterations keep the paper's inverse-linear coded gain and only pay the
    stand-ins' unicast hand-over overhead;
  * **uncoded fallback** (the legacy behavior, `mode="uncoded"`): every
    post-failure iteration ships the degraded missing set as unicast.

Reported per m: first post-failure Shuffle bits (= `recovery_bits`), total
job bits, wall-clock, and the repair-vs-fresh-recompile plan times. The
sweep asserts the coded path's bits stay strictly below the fallback's for
every m < r, and that both end states stay bitwise equal to the
single-machine oracle.

The smoke row is the CI-gated `scale_recovery_*` record in
`BENCH_scale.json` (`benchmarks/check_regression.py`).
"""
from __future__ import annotations

import pathlib
import sys

import numpy as np

try:
    from repro.core import algorithms as algo
except ImportError:
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]
    from repro.core import algorithms as algo

from repro import graphs, obs
from repro.core.allocation import divisible_n, er_allocation
from repro.core.faults import degrade_allocation, run_with_failure
from repro.core.shuffle_plan import compile_plan_csr


def run(report, smoke=False):
    n_req, K, r, p = (240, 6, 3, 0.15) if smoke else (1200, 10, 3, 0.04)
    iters, fail_at = (4, 1) if smoke else (10, 3)
    n = divisible_n(n_req, K, r)
    prog = algo.pagerank()
    g = graphs.erdos_renyi(n, p, seed=11)
    alloc = er_allocation(n, K, r)
    oracle = algo.reference_run(prog, g, iters, path="sparse")
    plan = compile_plan_csr(g.csr, alloc)
    rows = []
    for m in range(1, r):
        failed = tuple(range(m))

        with obs.stopwatch() as sw_c:
            res_c, st_c = run_with_failure(prog, g, alloc, iters, failed,
                                           fail_at_iter=fail_at)
        t_coded = sw_c.s
        with obs.stopwatch() as sw_u:
            res_u, st_u = run_with_failure(prog, g, alloc, iters, failed,
                                           fail_at_iter=fail_at,
                                           mode="uncoded")
        t_uncoded = sw_u.s
        assert np.array_equal(res_c.state, oracle), "coded failover != oracle"
        assert np.array_equal(res_u.state, oracle), "uncoded failover != oracle"
        assert st_c.recovery_bits < st_u.recovery_bits, \
            (m, st_c.recovery_bits, st_u.recovery_bits)
        assert res_c.shuffle_bits < res_u.shuffle_bits, \
            (m, res_c.shuffle_bits, res_u.shuffle_bits)

        # Plan surgery vs recompiling from scratch on the degraded alloc.
        with obs.stopwatch() as sw_rep:
            rep, degraded, rstats = plan.repair(g.csr, alloc, failed)
        t_repair = sw_rep.s
        with obs.stopwatch() as sw_fresh:
            compile_plan_csr(g.csr, degrade_allocation(alloc, failed)[0],
                             validate=False)
        t_fresh = sw_fresh.s

        gain = st_u.recovery_bits / st_c.recovery_bits
        report(f"recovery_f{m}", t_coded / iters * 1e6,
               f"recovery_bits coded={st_c.recovery_bits} "
               f"uncoded={st_u.recovery_bits} gain={gain:.2f}x "
               f"handover={rstats.handover_bits} "
               f"total coded={res_c.shuffle_bits} "
               f"uncoded={res_u.shuffle_bits} "
               f"repair_ms={t_repair * 1e3:.1f} "
               f"recompile_ms={t_fresh * 1e3:.1f}")
        rows.append({"failed": m, "coded_bits": res_c.shuffle_bits,
                     "uncoded_bits": res_u.shuffle_bits,
                     "recovery_coded": st_c.recovery_bits,
                     "recovery_uncoded": st_u.recovery_bits,
                     "s_coded": t_coded, "s_uncoded": t_uncoded,
                     "s_repair": t_repair, "s_recompile": t_fresh})
    report(f"scale_recovery_coded_n{n}",
           rows[0]["s_coded"] / iters * 1e6,
           f"K={K} r={r} |failed|=1 recovery gain="
           f"{rows[0]['recovery_uncoded'] / rows[0]['recovery_coded']:.2f}x "
           f"coded-repair failover (PR 7)")
    return {"n": n, "K": K, "r": r, "rows": rows}


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    smoke = "--smoke" in sys.argv[1:]

    def _report(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}", flush=True)

    run(_report, smoke=smoke)
