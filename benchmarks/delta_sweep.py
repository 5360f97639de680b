"""Incremental plan maintenance: `apply_delta` vs recompiling from scratch.

The PR 9 tentpole makes a compiled `ShufflePlan` follow a mutating graph in
O(plan + |delta|): `CSR.apply_delta` splices the sorted edge streams and
`ShufflePlan.apply_delta` splices every plan array in place of the fresh
lexsort + group-scan pipeline, under the locked contract that the result is
*bitwise identical* to `compile_plan_csr` on the mutated graph.

The sweep holds n ~ 1e5 fixed and grows the batch |delta| from 0.1% to 1%
of the edge set. Per point it reports the incremental wall-clock (plan-only
and including the CSR + edge-table splice) against a fresh compile, asserts
the bitwise contract on the largest batch, and asserts the acceptance gate:
>= 10x faster than recompiling while |delta| <= 1% of edges.

The smoke row is the CI-gated `scale_delta_pagerank_*` record in
`BENCH_scale.json` (`benchmarks/check_regression.py`); smoke mode also
closes the loop through `CompiledEngine.update` against a fresh session.
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
from repro.core import engine
from repro.core.allocation import divisible_n, er_allocation
from repro.core.graph_models import Graph
from repro.core.shuffle_plan import compile_plan_csr

GATE = 10.0          # acceptance: >= 10x vs fresh recompile at |delta| <= 1%


def _mk_delta(g, frac, rng):
    """Balanced batch mutating `frac` of the undirected edge set."""
    csr = g.csr
    m = csr.nnz // 2
    k = max(1, int(m * frac) // 2)
    up = csr.rows < csr.indices                 # one direction per edge
    eids = np.flatnonzero(up)
    dels = eids[rng.choice(eids.size, size=k, replace=False)]
    delete = list(zip(csr.rows[dels].tolist(), csr.indices[dels].tolist()))
    have = set(zip(csr.rows.tolist(), csr.indices.tolist()))
    insert, seen = [], set()
    while len(insert) < k:
        u, v = int(rng.integers(g.n)), int(rng.integers(g.n))
        if u == v:
            continue
        key = (min(u, v), max(u, v))
        if key in seen or (u, v) in have:
            continue
        seen.add(key)
        insert.append(key)
    return graphs.EdgeDelta.for_graph(g, insert=insert, delete=delete)


def _best_of(reps, *fns):
    """Best wall-clock per function, interleaved so background-load noise
    lands on every contestant equally. Returns ([best..], [last_out..])."""
    best = [float("inf")] * len(fns)
    outs = [None] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            with obs.stopwatch() as sw:
                outs[i] = fn()
            best[i] = min(best[i], sw.s)
    return best, outs


def run(report, smoke=False):
    n_req, K, r, reps = (240, 4, 2, 3) if smoke else (100_000, 4, 2, 7)
    fracs = [0.01] if smoke else [0.001, 0.005, 0.01]
    n = divisible_n(n_req, K, r)
    rng = np.random.default_rng(7)
    g = graphs.erdos_renyi(n, 10 / n, seed=7)
    alloc = er_allocation(n, K, r)
    plan = compile_plan_csr(g.csr, alloc)
    plan.edge_tables(g.csr, alloc)

    rows = []
    for frac in fracs:
        delta = _mk_delta(g, frac, rng)
        csr2 = g.csr.apply_delta(delta)

        def _full():                         # CSR + plan + edge tables
            csr_full = g.csr.apply_delta(delta)
            return plan.apply_delta(g.csr, alloc, delta, csr_new=csr_full)

        (t_plan, t_fresh, t_full), (out, _, _) = _best_of(
            reps,
            lambda: plan.apply_delta(g.csr, alloc, delta),
            lambda: compile_plan_csr(g.csr, alloc),
            _full)
        plan2, dstats = out
        speedup = t_fresh / t_plan
        assert dstats.schedule_changed
        assert speedup >= GATE or smoke, (
            f"|delta|={frac:.1%}: apply_delta only {speedup:.1f}x faster "
            f"than fresh compile (gate {GATE:.0f}x)")
        report(f"delta_plan_f{frac:g}", t_plan * 1e6,
               f"n={n} nnz={g.csr.nnz} |delta|={len(delta)} "
               f"plan_ms={t_plan * 1e3:.1f} full_ms={t_full * 1e3:.1f} "
               f"fresh_ms={t_fresh * 1e3:.1f} speedup={speedup:.1f}x")
        rows.append({"frac": frac, "delta": len(delta), "s_plan": t_plan,
                     "s_full": t_full, "s_fresh": t_fresh,
                     "speedup": speedup})
        if frac == fracs[-1]:                # bitwise gate, largest batch
            fresh = compile_plan_csr(csr2, alloc)
            for f in ("pair_k", "pair_i", "pair_j", "slot_pair", "pos_left",
                      "col_sender", "pair_col", "pair_slot", "all_k"):
                a, b = getattr(plan2, f), getattr(fresh, f)
                assert a.dtype == b.dtype and np.array_equal(a, b), f

    if smoke:       # end-to-end: a mutated session == a fresh session
        prog = algo.pagerank()
        eng = engine.compile(prog, g, alloc, "coded", path="sparse")
        delta = _mk_delta(g, 0.01, rng)
        with obs.stopwatch() as sw_upd:
            eng2 = eng.update(delta)
        g2 = Graph(model=g.model, params=dict(g.params),
                   csr=g.csr.apply_delta(delta))
        want = engine.compile(prog, g2, alloc, "coded", path="sparse").run(4)
        got = eng2.run(4)
        assert np.array_equal(got.state, want.state)
        assert got.shuffle_bits == want.shuffle_bits
        report(f"scale_delta_pagerank_n{n}", sw_upd.s * 1e6,
               f"K={K} r={r} |delta|={len(delta)} engine.update == fresh "
               f"session, plan speedup={rows[-1]['speedup']:.1f}x (PR 9)")
    return {"n": n, "K": K, "r": r, "s_fresh": t_fresh, "rows": rows}


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    smoke = "--smoke" in sys.argv[1:]

    def _report(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}", flush=True)

    run(_report, smoke=smoke)
