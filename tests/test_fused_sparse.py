"""Sparse multi-device coded Shuffle parity (shard_map, 8 forced host devices).

The fused sparse path (`fused_shuffle.FusedSparseShuffle`) must deliver
*bitwise-identical* uint32 words to the NumPy plan executor
(`ShufflePlan.execute_coded_sparse`) - across all four graph models x
{pagerank, sssp}, all three encode routes (batched xor_code jnp oracle,
Pallas kernel, plain jnp), the unicast-leftover spill, and the full
`engine.run(path="sparse", backend="fused")` loop - while constructing no
[n, n]-shaped array anywhere (schedule shape-guard + dense-materialization
guard + tracemalloc enforced, including at n > dense_limit).

Runs in subprocesses so the 8-device host-platform flag never leaks into
other tests; HOME and JAX_PLATFORMS=cpu are passed through per the ROADMAP
note (jax device probing hangs without them).
"""
import dataclasses
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro import graphs
from repro.core import algorithms as algo
from repro.core import engine
from repro.core.allocation import (bipartite_allocation, divisible_n,
                                   er_allocation)
from repro.core import fused_shuffle
from repro.core.fused_shuffle import (FusedSparseShuffle, gather_deliveries,
                                      partition_plan, receiver_spans)
from repro.core.graph_models import Graph
from repro.core.shuffle_plan import (compile_hierarchical, compile_plan_csr,
                                     resolve_topology)
from repro.graphs import EdgeDelta
from repro.launch.mesh import Topology

PREAMBLE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import tracemalloc
import numpy as np

from repro import graphs
from repro.core import algorithms as algo
from repro.core import engine, faults
from repro.core import graph_models as gm
from repro.core.allocation import (bipartite_allocation, divisible_n,
                                   er_allocation)
from repro.core.bitcodec import floats_to_words
from repro.core.fused_shuffle import FusedSparseShuffle
from repro.core.shuffle_plan import compile_plan_csr

out = {}


def case(model):
    # CSR-native graphs (repro.graphs streaming samplers) - the fused path
    # never needs a dense view.
    if model == "er":
        n = divisible_n(48, 4, 2)
        return graphs.erdos_renyi(n, 0.2, seed=11), er_allocation(n, 4, 2)
    if model == "pl":
        n = divisible_n(60, 4, 2)
        return graphs.power_law(n, 2.5, seed=9), er_allocation(n, 4, 2)
    if model == "rb":
        return (graphs.random_bipartite(48, 24, 0.3, seed=5),
                bipartite_allocation(48, 24, 6, 2))
    if model == "sbm":
        return (graphs.stochastic_block(48, 24, 0.25, 0.1, seed=5),
                bipartite_allocation(48, 24, 6, 2))
    raise ValueError(model)


def parity(g, alloc, prog, iters=2, **kw):
    # Two iterations replay the same jitted exchange on fresh values - the
    # compile-once/execute-many contract, checked word-for-word per round.
    plan = compile_plan_csr(g.csr, alloc)
    tables = plan.edge_tables(g.csr, alloc)
    fx = FusedSparseShuffle(plan, g.csr, alloc, **kw)
    state = prog.init(g)
    ok = True
    for _ in range(iters):
        ev = prog.map_edge_values(g, state).astype(np.float32)
        ref = plan.execute_coded_sparse(ev, tables)
        res = fx.execute(ev)
        ok = ok and np.array_equal(floats_to_words(ref.values),
                                   floats_to_words(res.values))
        ok = ok and ref.bits_sent == res.bits_sent
        buf = np.concatenate([ev, ref.values])
        state = prog.reduce_edges(buf[tables.gather], g.csr.indptr, state, g)
    return bool(ok)
"""

SCRIPT_PARITY = PREAMBLE + r"""
for model in ("er", "rb", "sbm", "pl"):
    g, alloc = case(model)
    for prog in (algo.pagerank(), algo.sssp(0)):
        out[f"{model}_{prog.name}"] = parity(g, alloc, prog)

# Unicast-leftover spill (bipartite r > K2: cluster-2 batches uncovered).
g, alloc = (graphs.random_bipartite(48, 24, 0.3, seed=5),
            bipartite_allocation(48, 24, 6, 3))
plan = compile_plan_csr(g.csr, alloc)
out["spill_has_leftovers"] = bool(plan.left_k.size > 0)
out["spill_pagerank"] = parity(g, alloc, algo.pagerank())
out["spill_sssp"] = parity(g, alloc, algo.sssp(0))

# Encode routes: Pallas kernel (interpret) and plain jnp vs the default.
g, alloc = case("er")
out["encode_xor_kernel"] = parity(g, alloc, algo.pagerank(), iters=1,
                                  encode="xor-kernel")
out["encode_jnp"] = parity(g, alloc, algo.pagerank(), iters=1, encode="jnp")

# A flat K = 6 exchange on a mesh of 6 of the 8 devices.
n = divisible_n(60, 6, 2)
out["flat_k6"] = parity(graphs.erdos_renyi(n, 0.25, seed=5),
                        er_allocation(n, 6, 2), algo.pagerank())

# B=8 payload columns, then a rebind onto a plan whose partition has other
# padded shapes (a denser graph on the same allocation), at B=1 and B=8.
def words_equal(plan, csr, fx, vals):
    ref = plan.execute_coded_sparse(vals, plan.edge_tables(csr, alloc))
    return bool(np.array_equal(floats_to_words(ref.values),
                               floats_to_words(fx.execute(vals).values)))


rng = np.random.default_rng(2)
g, alloc = case("er")
plan = compile_plan_csr(g.csr, alloc)
fx = FusedSparseShuffle(plan, g.csr, alloc)
out["b8"] = words_equal(plan, g.csr, fx,
                        rng.random((g.csr.nnz, 8), np.float32))
g2 = graphs.erdos_renyi(g.n, 0.4, seed=12)
plan2 = compile_plan_csr(g2.csr, alloc)
fx2 = fx.rebind(plan2, g2.csr, alloc)
out["rebind_reshaped"] = bool(fx2.operand_shape() != fx.operand_shape())
for B in (None, 8):
    vals = rng.random((g2.csr.nnz,) + ((B,) if B else ()), np.float32)
    out[f"rebind_b{B or 1}"] = words_equal(plan2, g2.csr, fx2, vals)

# Mid-run failure recovery rides the same CSR plans on this 8-device host.
g, alloc = case("er")
res_f, stats = faults.run_with_failure(algo.pagerank(), g, alloc, 3,
                                       failed=(1,), fail_at_iter=1)
out["faults_bitwise"] = bool(np.array_equal(
    res_f.state, algo.reference_run(algo.pagerank(), g, 3, path="sparse")))
out["faults_recovery_bits"] = int(stats.recovery_bits)
print(json.dumps(out))
"""

SCRIPT_ENGINE = PREAMBLE + r"""
# --- acceptance: 10-iteration coded PageRank, fused == numpy, K = 8 ---
K, r = 8, 3
n = divisible_n(280, K, r)
g0 = graphs.erdos_renyi(n, 0.15, seed=3)
# dense_limit=1: ANY [n, n] materialization anywhere on the path raises.
g = gm.Graph(model=g0.model, params=g0.params, csr=g0.csr, dense_limit=1)
alloc = er_allocation(n, K, r)
prog = algo.pagerank()
plan = compile_plan_csr(g.csr, alloc)
rn = engine.run(prog, g, alloc, 10, mode="coded", plan=plan, path="sparse")
rf = engine.run(prog, g, alloc, 10, mode="coded", plan=plan, path="sparse",
                backend="fused")
out["engine_10it_bitwise"] = bool(np.array_equal(
    floats_to_words(rn.state), floats_to_words(rf.state)))
out["engine_bits_equal"] = bool(rn.shuffle_bits == rf.shuffle_bits)
out["guard_held"] = True
try:
    g.adj
    out["guard_held"] = False
except ValueError:
    pass

# --- n > dense_limit: the path that used to be capped at toy n ---
K, r = 8, 2
n = divisible_n(21000, K, r)
assert n > gm.DENSE_LIMIT
g = graphs.erdos_renyi(n, 6.0 / n, seed=7)     # default guard active (n>2e4)
alloc = er_allocation(n, K, r)
tracemalloc.start()
plan = compile_plan_csr(g.csr, alloc)
tables = plan.edge_tables(g.csr, alloc)
fx = FusedSparseShuffle(plan, g.csr, alloc)
ev = prog.map_edge_values(g, prog.init(g)).astype(np.float32)
ref = plan.execute_coded_sparse(ev, tables)
res = fx.execute(ev)
_, peak = tracemalloc.get_traced_memory()
tracemalloc.stop()
out["scale_words_bitwise"] = bool(np.array_equal(
    floats_to_words(ref.values), floats_to_words(res.values)))
nnz, M = g.csr.nnz, int(plan.all_k.size)
out["scale_peak_mb"] = peak / 1e6
out["scale_peak_o_edges"] = bool(peak < 1500 * nnz)   # O(nnz+plan), not O(n^2)
out["scale_peak_below_dense"] = bool(peak < n * n)    # any [n,n] f32 would trip

# Shape guard: every partitioned table is [nnz]/[plan]-sized and the
# per-device rows are 1/K slices (+ padding slack) - nothing O(n^2)-shaped.
s = fx.sched
arrays = [s.enc_e, s.enc_shift, s.enc_mask, s.dec_s, s.dec_w,
          s.dec_mask, s.dec_shift, s.strip_e, s.strip_shift, s.strip_mask]
out["tables_not_dense"] = bool(all(a.size < n * n // 8 for a in arrays))
C = int(plan.col_sender.size) + int(plan.left_k.size)
out["per_device_loc"] = bool(s.Lmax <= 2 * r * nnz // K + 8)
out["per_device_cols"] = bool(s.W <= 2 * C // K + 8)
out["per_device_deliveries"] = bool(s.Dmax <= 2 * M // K + 8)

# Placement: each device holds its own server's block of every table, so
# nothing is staged whole on one device and resharded per call.
out["tables_placed_per_device"] = bool(all(
    len({sh.device.id for sh in a.addressable_shards}) == K
    and all(sh.data.shape[0] == 1 for sh in a.addressable_shards)
    for a in fx._dev_tables))
print(json.dumps(out))
"""


def _run(script, timeout=900):
    # HOME must survive (jax device init blocks without a resolvable home
    # dir), and the CPU platform must be pinned so jax does not probe for
    # an accelerator the sandbox cannot initialize.
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=timeout,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                               "HOME": os.environ.get("HOME", "/tmp"),
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_fused_sparse_word_parity_models_programs_and_spill():
    res = _run(SCRIPT_PARITY)
    for model in ("er", "rb", "sbm", "pl"):
        for prog in ("pagerank", "sssp"):
            assert res[f"{model}_{prog}"], (model, prog)
    assert res["spill_has_leftovers"]          # the case really spills
    assert res["spill_pagerank"] and res["spill_sssp"]
    assert res["encode_xor_kernel"] and res["encode_jnp"]
    assert res["flat_k6"]                      # K = 6 on 6 of 8 devices
    assert res["b8"]                           # batched payload, flat
    assert res["rebind_reshaped"]              # the rebind changes shapes
    assert res["rebind_b1"] and res["rebind_b8"]
    assert res["faults_bitwise"]
    assert res["faults_recovery_bits"] > 0


def test_fused_engine_acceptance_and_beyond_dense_limit():
    res = _run(SCRIPT_ENGINE)
    assert res["engine_10it_bitwise"]          # acceptance criterion
    assert res["engine_bits_equal"]
    assert res["guard_held"]                   # no [n, n] ever materialized
    assert res["scale_words_bitwise"]          # n > dense_limit, bit-exact
    assert res["scale_peak_o_edges"], res["scale_peak_mb"]
    assert res["scale_peak_below_dense"]
    assert res["tables_not_dense"]
    assert res["per_device_loc"]
    assert res["per_device_cols"]
    assert res["per_device_deliveries"]
    assert res["tables_placed_per_device"]


@pytest.mark.parametrize("case", ["r1", "r2", "r3", "spill"])
def test_slot_entries_are_the_plans_csr_entries_of_mapped_values(case):
    """The flat exchange's host gather reads edge words through `enc_e` and
    `strip_e`. Each entry is the zero sentinel `nnz` exactly where its mask
    is 0; every other entry is a CSR entry whose column vertex the server
    Mapped, and it is the plan's `pair_e` / `left_e` of that slot, checked
    here slot by slot against the plan's columns and deliveries. r = 1 has
    no strip slots; the bipartite spill has unicast leftovers."""
    if case == "spill":
        g = graphs.random_bipartite(48, 24, 0.3, seed=5)
        alloc = bipartite_allocation(48, 24, 6, 3)
    else:
        n = divisible_n(60, 4, int(case[1]))
        g = graphs.erdos_renyi(n, 0.2, seed=4)
        alloc = er_allocation(n, 4, int(case[1]))
    K, r = alloc.K, alloc.r
    plan = compile_plan_csr(g.csr, alloc)
    assert (plan.left_k.size > 0) == (case == "spill")
    t = plan.edge_tables(g.csr, alloc)
    s = partition_plan(plan, g.csr, alloc)
    nnz = g.csr.nnz
    for ent, mask in ((s.enc_e, s.enc_mask), (s.strip_e, s.strip_mask)):
        assert ent.dtype == np.int32
        assert np.array_equal(ent == nnz, mask == 0)
        for k in range(K):
            real = ent[k][ent[k] != nnz]
            assert alloc.map_sets[k, g.csr.indices[real]].all()
    assert (s.enc_e != nnz).any()
    assert ((s.strip_e != nnz).any() if r > 1 else s.strip_e.size == 0)

    # Encode: sender k's coded columns in (group, rank) order, then the
    # leftovers whose smallest Mapping server is k, in plan order.
    Pn = plan.pair_k.size
    for k in range(K):
        cols = [c for c in range(plan.col_sender.size)
                if plan.col_sender[c] == k]
        cols.sort(key=lambda c: (plan.col_gm[c], plan.col_rank[c]))
        want = [[t.pair_e[p] if p < Pn else nnz for p in plan.slot_pair[c]]
                for c in cols]
        want += [[t.left_e[m]] + [nnz] * (r - 1)
                 for m in range(plan.left_k.size)
                 if np.argmax(alloc.map_sets[:, plan.left_j[m]]) == k]
        assert np.array_equal(s.enc_e[k, :len(want)], np.reshape(want, (-1, r)))
        assert (s.enc_e[k, len(want):] == nnz).all()

    # Strip: delivery m of receiver k, segment t, strips the other slots
    # of the column that carries it, in slot order.
    for p in range(Pn):
        m = plan.pos_covered[p]
        k = plan.all_k[m]
        for seg in range(r):
            c, own = plan.pair_col[p, seg], plan.pair_slot[p, seg]
            want = [t.pair_e[q] if q < Pn else nnz
                    for slot, q in enumerate(plan.slot_pair[c]) if slot != own]
            assert list(s.strip_e[k, m - plan.ptr[k], seg]) == want


def _plan_of(kind):
    """(plan, csr, alloc) from one plan constructor: a fresh compile, a
    repair with server 1 failed, an edge delta, or a two-level plan."""
    K, r = 4, 2
    n = divisible_n(60, K, r)
    g = graphs.erdos_renyi(n, 0.2, seed=4)
    alloc = er_allocation(n, K, r)
    csr, plan = g.csr, compile_plan_csr(g.csr, alloc)
    if kind == "repair":
        plan, alloc, _ = plan.repair(csr, alloc, (1,))
    elif kind == "delta":
        e = np.flatnonzero(csr.rows < csr.indices)[:3]
        have = set(zip(csr.rows.tolist(), csr.indices.tolist()))
        ins = [(u, v) for u in range(n) for v in range(u + 1, n)
               if (u, v) not in have][:3]
        delta = EdgeDelta.for_graph(
            g, insert=ins, delete=list(zip(csr.rows[e], csr.indices[e])))
        csr2 = csr.apply_delta(delta)
        plan, _ = plan.apply_delta(csr, alloc, delta, csr_new=csr2)
        csr = csr2
    elif kind == "hier":
        plan = compile_hierarchical(csr, alloc, Topology(2, 2))
    return plan, csr, alloc


@pytest.mark.parametrize("B", [None, 8])
@pytest.mark.parametrize("kind", ["compile", "repair", "delta", "hier"])
def test_reindex_is_per_receiver_copies_bitwise(kind, B):
    """The pull-back re-index copies each receiver's leading `count` words
    of its padded row; it equals the fancy index
    `out[all_k, ..., arange(M) - ptr[all_k]]` bitwise, padding garbage."""
    plan, _, _ = _plan_of(kind)
    plan = plan.flat if kind == "hier" else plan
    K, M = plan.K, plan.all_k.size
    counts = np.diff(plan.ptr)
    assert np.array_equal(plan.all_k, np.repeat(np.arange(K), counts))
    starts, got = receiver_spans(plan)
    assert np.array_equal(starts, plan.ptr[:-1])
    assert np.array_equal(got, counts)
    if kind == "repair":
        assert counts[1] == 0                  # the failed server receives none
    lead = () if B is None else (B,)
    rng = np.random.default_rng(7)
    out = rng.integers(0, 2**32, size=(K,) + lead + (counts.max() + 3,),
                       dtype=np.uint32)
    old = out[plan.all_k, ..., np.arange(M, dtype=np.int64)
              - plan.ptr[plan.all_k]]
    new = gather_deliveries(out, starts, counts)
    assert new.shape == old.shape == (M,) + lead
    assert new.dtype == np.uint32 and new.flags.c_contiguous
    assert np.array_equal(new, old)


@pytest.mark.parametrize("kind", ["compile", "delta", "hier"])
def test_bind_keeps_the_receiver_spans_of_its_flat_plan(kind):
    """`_bind` (no devices) derives the spans once, from the flat plan the
    exchange delivers in, and every span fits the padded row."""
    ex = object.__new__(FusedSparseShuffle)
    ex._bind(*_plan_of(kind), None)
    assert ex._hier == (kind == "hier")
    assert np.array_equal(ex._starts, ex.plan.ptr[:-1])
    assert np.array_equal(ex._counts, np.diff(ex.plan.ptr))
    assert ex._counts.max() <= ex.sched.Dmax


def test_bind_refuses_deliveries_not_grouped_by_receiver():
    plan, csr, alloc = _plan_of("compile")
    a = int(plan.ptr[1])                       # first of receiver 1
    all_k = plan.all_k.copy()
    all_k[[a - 1, a]] = all_k[[a, a - 1]]      # one delivery each way
    bad = dataclasses.replace(plan, all_k=all_k)
    with pytest.raises(ValueError, match="not grouped by receiver"):
        object.__new__(FusedSparseShuffle)._bind(bad, csr, alloc, None)


@pytest.mark.parametrize("case", ["topology_disagrees", "flat_plan"])
def test_every_entry_point_refuses_a_plan_its_topology_cannot_run(case):
    """One rule reconciles a plan with a `Topology`: a `HierarchicalPlan`
    refuses a topology other than its own, and a non-flat topology
    refuses a flat `ShufflePlan`. The engine, the partition and the
    exchange's bind all apply it."""
    plan, csr, alloc = _plan_of("hier")
    if case == "topology_disagrees":
        topo, match = Topology.flat(4), "disagrees with the plan's"
    else:
        plan, topo, match = plan.flat, Topology(2, 2), "needs a HierarchicalPlan"
    with pytest.raises(ValueError, match=match):
        resolve_topology(plan, topo)
    with pytest.raises(ValueError, match=match):
        partition_plan(plan, csr, alloc, topo)
    with pytest.raises(ValueError, match=match):
        object.__new__(FusedSparseShuffle)._bind(plan, csr, alloc, topo)
    g = Graph(model="er", params={}, csr=csr)
    with pytest.raises(ValueError, match=match):
        engine.compile(algo.pagerank(), g, alloc, "coded", plan=plan,
                       topology=topo)


SCRIPT_POOLED = PREAMBLE + r"""
from repro.core import fused_shuffle

# Chunk threshold lowered below the rows of these graphs and 6 threads,
# whatever the host has, so every flat gather below runs on the pool.
fused_shuffle._MIN_CHUNK = 8
fused_shuffle._gather_threads = lambda: 6
rng = np.random.default_rng(3)
for r in (2, 3):
    n = divisible_n(60, 4, r)
    g = graphs.erdos_renyi(n, 0.2, seed=4)
    alloc = er_allocation(n, 4, r)
    plan = compile_plan_csr(g.csr, alloc)
    fx = FusedSparseShuffle(plan, g.csr, alloc)
    out[f"r{r}_slices"] = int(fx._row_cuts.size - 1)
    for B in (None, 8):
        vals = rng.random((g.csr.nnz,) + ((B,) if B else ()), np.float32)
        ref = plan.execute_coded_sparse(vals, plan.edge_tables(g.csr, alloc))
        out[f"r{r}_b{B or 1}"] = bool(np.array_equal(
            floats_to_words(ref.values),
            floats_to_words(fx.execute(vals).values)))

g, alloc = case("er")
plan = compile_plan_csr(g.csr, alloc)
rn = engine.run(algo.pagerank(), g, alloc, 3, mode="coded", plan=plan,
                path="sparse")
rf = engine.run(algo.pagerank(), g, alloc, 3, mode="coded", plan=plan,
                path="sparse", backend="fused")
out["engine"] = bool(np.array_equal(floats_to_words(rn.state),
                                    floats_to_words(rf.state)))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def pooled():
    return _run(SCRIPT_POOLED)


@pytest.mark.parametrize("case", ["r2_b1", "r2_b8", "r3_b1", "r3_b8",
                                  "engine"])
def test_pooled_gather_delivers_the_plan_executors_words(pooled, case):
    """With the chunk threshold lowered, a session's flat gather runs in
    several row slices per server on the pool, and the delivered words (and
    a 3-iteration PageRank) are bitwise the NumPy plan executor's."""
    assert pooled["r2_slices"] > 1 and pooled["r3_slices"] > 1
    assert pooled[case]


@pytest.mark.parametrize("chunks", ["one", "several", "ragged"])
@pytest.mark.parametrize("B", [None, 8])
@pytest.mark.parametrize("r", [1, 2, 3])
def test_pooled_slot_words_are_the_serial_take_bitwise(monkeypatch, r, B,
                                                       chunks):
    """`_slot_words` (no devices) against one serial `np.take` per server
    through `_slot_e`, as the flat operand was first built: at the test
    graph's own single chunk (serial), and with the chunk threshold lowered
    to several slices per server that divide N, or that do not."""
    n = divisible_n(60, 4, r)
    g = graphs.erdos_renyi(n, 0.2, seed=4)
    alloc = er_allocation(n, 4, r)
    plan = compile_plan_csr(g.csr, alloc)
    bind = (plan, g.csr, alloc, None)
    ex = object.__new__(FusedSparseShuffle)
    ex._bind(*bind)
    K, N = ex._slot_e.shape
    if chunks != "one":
        start = 2 if chunks == "several" else 3
        p = next(p for p in range(start, N) if (N % p == 0) == (chunks == "several"))
        monkeypatch.setattr(fused_shuffle, "_MIN_CHUNK", 1)
        monkeypatch.setattr(fused_shuffle, "_gather_threads", lambda: K * p)
        ex._bind(*bind)
        widths = np.diff(ex._row_cuts)
        assert widths.size == p and widths.sum() == N
        assert (widths.min() == widths.max()) == (chunks == "several")
    workers = ex._workers(B or 1)
    assert (workers == 1) == (chunks == "one")
    rng = np.random.default_rng(r)
    for _ in range(2):                    # the second call reuses the buffers
        ew = rng.integers(0, 2**32, (g.csr.nnz,) + ((B,) if B else ()),
                          dtype=np.uint32)
        src = np.concatenate([np.atleast_2d(ew.T),
                              np.zeros((B or 1, 1), np.uint32)], axis=1)
        src = src[0] if B is None else src
        want = np.stack([np.take(src, idx, axis=-1) for idx in ex._slot_e])
        got = ex._slot_words(ew, workers)
        assert got.shape == want.shape == ex.operand_shape(B)
        assert np.array_equal(got, want)


def test_two_exchanges_gather_on_the_shared_pool_at_once(monkeypatch):
    """Calls on two exchanges may overlap: they share the gather pool but
    write their own buffers. Two threads, each calling its own exchange
    with more jobs than cores under a short switch interval, get the serial
    words on every call."""
    cores = os.cpu_count() or 1
    monkeypatch.setattr(fused_shuffle, "_MIN_CHUNK", 1)
    monkeypatch.setattr(fused_shuffle, "_gather_threads", lambda: 4 * cores)
    exs = []
    for r in (2, 3):
        n = divisible_n(60, 4, r)
        g = graphs.erdos_renyi(n, 0.2, seed=4)
        alloc = er_allocation(n, 4, r)
        ex = object.__new__(FusedSparseShuffle)
        ex._bind(compile_plan_csr(g.csr, alloc), g.csr, alloc, None)
        assert ex._workers(1) > cores
        exs.append(ex)
    bad = []

    def loop(ex, seed):
        rng = np.random.default_rng(seed)
        for _ in range(100):
            ew = rng.integers(0, 2**32, ex._nnz, dtype=np.uint32)
            src = np.append(ew, np.uint32(0))
            want = np.stack([np.take(src, idx) for idx in ex._slot_e])
            if not np.array_equal(ex._slot_words(ew, ex._workers(1)), want):
                bad.append(seed)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=loop, args=(ex, i))
              for i, ex in enumerate(exs)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    assert bad == []
