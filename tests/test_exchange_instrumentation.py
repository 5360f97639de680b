"""Instrumentation of the fused coded Shuffle (4 forced host devices).

What the exchange reports about itself, flat and on `Topology(2, 2)`:

* the host spans of a `backend="fused"` session nest where the benchmark
  reads them (`plan.edge_tables` after `engine.compile`, `exchange.*`
  under `phase.exchange`, `decode.*` under `phase.decode`, `phase.codec`
  under `engine.iteration`);
* `exchange.first_call` marks exactly the calls whose shapes are new to the
  jitted exchange, across `rebind`;
* the registry counters equal their formulas, at B=1 and B=8;
* `phase.encode` names the threads its gather ran on (`workers`), and
  `exchange_encode_parallel_total` counts the calls that took more than one,
  on a small graph and with the gather's chunk threshold lowered;
* the three device scopes reach the lowered exchange;
* delivered words equal `ShufflePlan.execute_coded_sparse` at B=1, at B=8
  and after a rebind that reshapes the exchange;
* a disabled tracer leaves state and `shuffle_bits` bitwise unchanged.

Runs in one subprocess so the 4-device host-platform flag never leaks into
other tests (HOME + JAX_PLATFORMS=cpu passed through).
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro import graphs, obs
from repro.core import algorithms as algo
from repro.core import engine
from repro.core.allocation import divisible_n, er_allocation
from repro.core import fused_shuffle
from repro.core.bitcodec import floats_to_words
from repro.graphs import EdgeDelta
from repro.launch.mesh import Topology

COUNTERS = ("exchange_calls_total", "exchange_host_copy_bytes_total",
            "exchange_bits_useful_total", "exchange_bits_sent_total",
            "exchange_encode_parallel_total")


def counters(reg):
    return {c: reg.get(c).value if reg.get(c) else 0 for c in COUNTERS}


def dims(ex):
    s = ex.sched
    d = {"K": s.K, "Lmax": s.Lmax, "Dmax": s.Dmax,
         "schedule_bits": int(ex._schedule_bits)}
    if ex._hier:
        d.update(R=s.R, Wx=s.Wx)
    else:
        d.update(W=s.W, r=s.r)
    return d


def delivered_equal(sess, vals):
    # the session's fused exchange against the NumPy plan executor (two
    # levels count their bits per level, so only the words compare)
    ref = sess.plan.execute_coded_sparse(vals, sess.tables)
    res = sess.fused.execute(vals)
    return bool(np.array_equal(floats_to_words(ref.values),
                               floats_to_words(res.values)))


def report(topo):
    K, r = 4, 2
    n = divisible_n(120, K, r)
    g = graphs.erdos_renyi(n, 0.1, seed=3)
    alloc = er_allocation(n, K, r)
    prog = algo.pagerank()
    out = {}

    def compile_session():
        return engine.compile(prog, g, alloc, "coded", path="sparse",
                              backend="fused", topology=topo)

    # Untraced reference run on its own session.
    off = obs.Tracer(enabled=False)
    prev_tr = obs.set_tracer(off)
    plain = compile_session().run(2)
    out["disabled_collected"] = len(list(off.spans()))

    tr = obs.Tracer(enabled=True)
    obs.set_tracer(tr)
    reg = obs.MetricsRegistry()
    prev_reg = obs.set_registry(reg)
    try:
        sess = compile_session()
        ex = sess.fused
        res = sess.run(2)
        out["tree"] = tr.tree()
        out["encode_attrs"] = sorted(tr.find("phase.encode")[0].attrs)
        out["encode_workers"] = [sp.attrs["workers"]
                                 for sp in tr.find("phase.encode")]
        out["bitwise"] = bool(np.array_equal(floats_to_words(plain.state),
                                             floats_to_words(res.state)))
        out["bits_equal"] = bool(plain.shuffle_bits == res.shuffle_bits)
        out["dims"] = dims(ex)
        out["after_2"] = counters(reg)

        # A new payload shape compiles again: B=8.
        tr.reset()
        sess.with_program(algo.multi_sssp(list(range(8)))).run(1)
        out["after_b8"] = counters(reg)
        out["b8_first"] = len(tr.find("exchange.first_call"))
        out["b8_workers"] = [sp.attrs["workers"]
                             for sp in tr.find("phase.encode")]

        # rebind onto the same padded shapes: the compiled call is reused.
        tr.reset()
        plan = sess.hplan if sess.hplan is not None else sess.plan
        same = ex.rebind(plan, g.csr, alloc)
        same.execute(prog.map_edge_values(g, res.state).astype(np.float32))
        out["rebind_same"] = [len(tr.find("exchange.first_call")),
                              len(tr.find("exchange.run"))]

        # A delta that grows the device program's shapes (W and Dmax; the
        # flat program no longer reads Lmax) compiles once more, on the
        # rebound exchange, and only on its first call. Edges between the
        # first and the last vertices cross servers, so they are Shuffled.
        have = set(zip(g.csr.rows.tolist(), g.csr.indices.tolist()))
        ins = [(u, v) for u in range(12) for v in range(n - 12, n)
               if (u, v) not in have]
        upd = sess.update(EdgeDelta.for_graph(g, insert=ins))
        tr.reset()
        upd.run(2)
        out["dims_updated"] = dims(upd.fused)
        out["updated"] = [len(tr.find("exchange.first_call")),
                          len(tr.find("exchange.run"))]

        # The device scopes reach the lowered exchange.
        host = (jax.ShapeDtypeStruct(ex.operand_shape(), jnp.uint32),)
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                     sharding=NamedSharding(ex.mesh, p))
                for a, p in zip(host + ex._host_tables(), ex._in_specs())]
        text = ex._fn.lower(*args).as_text(debug_info=True)
        out["scopes"] = [s for s in ("exchange.encode", "exchange.gather",
                                     "exchange.decode") if s in text]

        # Delivered words, before and after the reshaping rebind.
        rng = np.random.default_rng(5)
        ev = prog.map_edge_values(g, res.state).astype(np.float32)
        out["parity"] = [
            delivered_equal(sess, ev),
            delivered_equal(sess, rng.random((g.csr.nnz, 8), np.float32)),
            delivered_equal(upd, rng.random(upd.g.csr.nnz, np.float32)),
            delivered_equal(upd, rng.random((upd.g.csr.nnz, 8), np.float32))]

        # The gather's chunk threshold lowered below the rows of this graph
        # (and 4 threads whatever the host has): a fresh session's gathers
        # run on the pool, at B=1 and B=8.
        low = (fused_shuffle._MIN_CHUNK, fused_shuffle._gather_threads)
        fused_shuffle._MIN_CHUNK = 16
        fused_shuffle._gather_threads = lambda: 4
        try:
            pooled = compile_session()
            tr.reset()
            before = counters(reg)
            pooled.run(2)
            pooled.with_program(algo.multi_sssp(list(range(8)))).run(1)
            after = counters(reg)
        finally:
            fused_shuffle._MIN_CHUNK, fused_shuffle._gather_threads = low
        out["pooled_workers"] = [sp.attrs["workers"]
                                 for sp in tr.find("phase.encode")]
        out["pooled_counts"] = [after[c] - before[c] for c in (
            "exchange_calls_total", "exchange_encode_parallel_total")]
    finally:
        obs.set_tracer(prev_tr)
        obs.set_registry(prev_reg)
    return out


print(json.dumps({"flat": report(None), "racks2x2": report(Topology(2, 2))}))
"""


@pytest.fixture(scope="module")
def reports():
    proc = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                          text=True, timeout=600,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                               "HOME": os.environ.get("HOME", "/tmp"),
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


TOPOLOGIES = ["flat", "racks2x2"]


def _node(name, *children):
    return [name, list(children)]


def _iteration(first: bool):
    exchange = ([_node("exchange.place_tables")] if first else []) + [
        _node("exchange.place"),
        _node("exchange.first_call" if first else "exchange.run")]
    return _node(
        "engine.iteration",
        _node("phase.map"), _node("phase.codec"), _node("phase.encode"),
        _node("phase.exchange", *exchange),
        _node("phase.decode", _node("decode.pull"), _node("decode.reindex")),
        _node("phase.codec"), _node("phase.reduce"))


@pytest.mark.parametrize("topo", TOPOLOGIES)
def test_session_span_tree_nests_the_new_spans(reports, topo):
    tree = reports[topo]["tree"]
    # engine.compile, then each level's edge tables (one flat, two on
    # racks: the server level, then the rack level), then the first
    # `.fused`'s partition, then the iterations.
    levels = 1 if topo == "flat" else 2
    assert tree[0][0] == "engine.compile"
    assert tree[1:] == ([_node("plan.edge_tables")] * levels
                        + [_node("exchange.partition"),
                           _node("engine.run", _iteration(True),
                                 _iteration(False))])


@pytest.mark.parametrize("topo", TOPOLOGIES)
def test_first_call_once_per_shape_and_after_a_reshaping_rebind(reports,
                                                                topo):
    rep = reports[topo]
    assert rep["b8_first"] == 1                  # new payload shape
    assert rep["rebind_same"] == [0, 1]          # same shapes: reused
    d0, d1 = rep["dims"], rep["dims_updated"]
    width = "Wx" if topo == "racks2x2" else "W"
    assert all(d0[k] != d1[k] for k in (width, "Dmax"))
    assert rep["updated"] == [1, 1]


def _expected(d: dict, B: int) -> dict:
    hier = "Wx" in d
    words = (d["R"] * d["Wx"] + d["K"] * (d["Lmax"] + 1) if hier
             else d["K"] * d["W"])
    # in: two levels, each server's local words and the sentinel; flat,
    # its encode and strip words in slot order. out: padded deliveries.
    operand = (d["Lmax"] + 1 if hier
               else d["r"] * d["W"] + d["r"] * (d["r"] - 1) * d["Dmax"])
    return {"exchange_calls_total": 1,
            "exchange_host_copy_bytes_total":
                4 * B * d["K"] * (operand + d["Dmax"]),
            "exchange_bits_useful_total": B * d["schedule_bits"],
            "exchange_bits_sent_total": 32 * B * words,
            "exchange_encode_parallel_total": 0}    # rows below a chunk


@pytest.mark.parametrize("topo", TOPOLOGIES)
def test_counters_equal_their_formulas_at_b1_and_b8(reports, topo):
    rep = reports[topo]
    one = _expected(rep["dims"], 1)
    assert rep["after_2"] == {k: 2 * v for k, v in one.items()}
    eight = _expected(rep["dims"], 8)
    assert rep["after_b8"] == {k: rep["after_2"][k] + v
                               for k, v in eight.items()}
    # the flat schedule's useful bits fit inside what the chips send
    sent = rep["after_2"]["exchange_bits_sent_total"]
    assert 0 < rep["after_2"]["exchange_bits_useful_total"] <= sent


@pytest.mark.parametrize("topo", TOPOLOGIES)
def test_encode_workers_and_the_parallel_counter(reports, topo):
    """The test graph's rows are shorter than one chunk, so every gather is
    serial. Under a lowered threshold the flat gather runs its K x B x
    slices on 4 threads and every flat call counts as parallel; the
    two-level gather has no pool."""
    rep = reports[topo]
    assert rep["encode_workers"] == [1, 1] and rep["b8_workers"] == [1]
    if topo == "flat":
        assert rep["pooled_workers"] == [4, 4, 4]
        assert rep["pooled_counts"] == [3, 3]
    else:
        assert rep["pooled_workers"] == [1, 1, 1]
        assert rep["pooled_counts"] == [3, 0]


@pytest.mark.parametrize("topo", TOPOLOGIES)
def test_device_scopes_reach_the_lowered_exchange(reports, topo):
    assert reports[topo]["scopes"] == ["exchange.encode", "exchange.gather",
                                       "exchange.decode"]


@pytest.mark.parametrize("topo", TOPOLOGIES)
def test_delivered_words_bitwise_at_b1_b8_and_after_a_reshaping_rebind(
        reports, topo):
    assert reports[topo]["parity"] == [True] * 4


@pytest.mark.parametrize("topo", TOPOLOGIES)
def test_disabled_tracer_is_bitwise_the_traced_run(reports, topo):
    rep = reports[topo]
    assert rep["disabled_collected"] == 0
    assert rep["bitwise"] and rep["bits_equal"]
    # what the traced run's encode span reports besides
    assert rep["encode_attrs"] == ["B", "backend", "nnz", "words", "workers"]
