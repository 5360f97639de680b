"""Fused (multi-device shard_map) vs NumPy sparse coded-Shuffle sweep.

Measures the steady-state per-iteration wall-clock of one coded Shuffle on
the sparse path, two ways off the *same* compiled plan:

  * `FusedSparseShuffle` replaying its jitted shard_map exchange on a
    K-device ('servers',) mesh (per-shard xor_code encode, one packed
    all_gather of uint32 words, per-shard strip/decode);
  * `ShufflePlan.execute_coded_sparse`, the single-host NumPy executor.

Bitwise parity of the delivered uint32 words is asserted on every case -
this is a benchmark of the *same* computation on two substrates, not of
two approximations.

Where it runs, chosen from the platform of JAX's devices:

  * On the CPU, the sweep runs in a child process pinned to
    JAX_PLATFORMS=cpu with XLA_FLAGS=--xla_force_host_platform_device_count=8
    (jax pins the device count at first init). The smoke row
    `scale_fused_pagerank_n280` is committed to BENCH_scale.json and gated
    by benchmarks/check_regression.py. Host-CPU collectives are NOT the TPU
    performance story - the record tracks regression of the fused path's
    compiled replay, while the numpy column is the reference point.
  * On accelerators it runs in this process on the devices present, with
    K set to their count (r capped at K - 1): a chip belongs to one
    process, so a child could not reach it. With one device there is no
    exchange to run; the sweep reports `fused_sweep_not_measured` and
    returns.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

SMOKE_CASES = [(280, 8, 3, 0.10)]          # n=280 (already divisible)
FULL_CASES = [(1000, 8, 3, 0.05), (3000, 8, 3, 0.02)]
HOST_DEVICES = 8


def sweep(cases) -> list[dict]:
    """Run each (n, K, r, p) case in this process; one row per case."""
    import numpy as np

    from repro import graphs, obs
    from repro.core import algorithms as algo
    from repro.core.allocation import divisible_n, er_allocation
    from repro.core.bitcodec import floats_to_words
    from repro.core.fused_shuffle import FusedSparseShuffle
    from repro.core.shuffle_plan import compile_plan_csr

    prog = algo.pagerank()
    rows = []
    for n_req, K, r, p in cases:
        n = divisible_n(n_req, K, r)
        g = graphs.erdos_renyi(n, p, seed=7)
        alloc = er_allocation(n, K, r)
        plan = compile_plan_csr(g.csr, alloc)
        tables = plan.edge_tables(g.csr, alloc)
        fx = FusedSparseShuffle(plan, g.csr, alloc)
        ev = prog.map_edge_values(g, prog.init(g)).astype(np.float32)

        ref = plan.execute_coded_sparse(ev, tables)
        res = fx.execute(ev)                       # includes jit compile
        equal = bool(np.array_equal(floats_to_words(ref.values),
                                    floats_to_words(res.values)))

        # One warmup + mean-of-5 for both substrates (shared obs helper;
        # the fused warmup rep is the steady-state replay, compile already
        # paid).
        numpy_us = obs.timeit(lambda: plan.execute_coded_sparse(ev, tables),
                              reps=5, warmup=1)
        fused_us = obs.timeit(lambda: fx.execute(ev), reps=5, warmup=1)

        rows.append({"n": n, "K": K, "r": r, "edges": int(g.num_edges),
                     "M": int(plan.all_k.size), "equal": equal,
                     "fused_us": fused_us, "numpy_us": numpy_us})
    return rows


def _sweep_in_child(cases) -> list[dict]:
    # Absolute src path: run.py supports plain-script invocation from any
    # cwd, so the subprocess env must not depend on the caller's cwd.
    root = pathlib.Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, __file__, json.dumps(cases)],
        capture_output=True, text=True, timeout=1200,
        env={"PYTHONPATH": str(root / "src"),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", "/tmp"), "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count="
                          f"{HOST_DEVICES}"})
    if proc.returncode != 0:
        raise RuntimeError(f"fused sweep subprocess failed:\n"
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(report, smoke=False):
    cases = SMOKE_CASES if smoke else SMOKE_CASES + FULL_CASES
    import jax

    devices = jax.devices()
    K, platform = len(devices), devices[0].platform
    if platform == "cpu":
        rows = _sweep_in_child(cases)
    elif K < 2:
        report("fused_sweep_not_measured", -1.0,
               f"the exchange needs >= 2 devices; found {K} {platform}")
        return {"rows": []}
    else:
        rows = sweep([(n, K, min(r, K - 1), p) for n, _, r, p in cases])
    for row in rows:
        assert row["equal"], f"fused != numpy words at n={row['n']}"
        report(f"scale_fused_pagerank_n{row['n']}", row["fused_us"],
               f"K={row['K']} r={row['r']} edges={row['edges']} "
               f"M={row['M']} numpy_us={row['numpy_us']:.1f} "
               f"vs_numpy={row['fused_us'] / max(row['numpy_us'], 1e-9):.1f}x "
               f"bitwise_equal={row['equal']}")
    return {"rows": rows}


if __name__ == "__main__":                 # the forced-host-device child
    print(json.dumps(sweep(json.loads(sys.argv[1]))))
