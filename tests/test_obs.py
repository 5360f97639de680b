"""Telemetry subsystem (PR 8): spans, metrics, and bench helpers.

Contracts under test:

* a **disabled** tracer is a hard no-op — `CompiledEngine.run` under it is
  bitwise identical to an untraced run, `span()` hands back one shared
  null-span singleton, and nothing is collected;
* an **enabled** tracer produces the deterministic pinned span tree for a
  seeded 3-iteration coded run — compile + all five Theorem-1 phases per
  iteration — including fault events and checkpoint spans, with the summed
  exchange-span bits equal to the run's `shuffle_bits` (the Definition-2
  numerator, denormalized via `loads()`);
* Chrome-trace export round-trips through JSON with the span structure;
* counters / gauges / histograms behave, quantiles interpolate, and the
  registry exports parseable Prometheus text;
* the shared bench helpers (`measure` / `timeit` / `stopwatch`) obey their
  warmup/reps/reduction semantics.
"""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import algorithms as algo
from repro.core import engine
from repro.core.allocation import divisible_n, er_allocation
from repro.core.checkpoint import SessionCheckpointer
from repro.core.faults import FaultSchedule
from repro.core.bitcodec import T_BITS


def _case(n=48, K=4, r=2, p=0.2, seed=11):
    from repro import graphs
    n = divisible_n(n, K, r)
    return graphs.erdos_renyi(n, p, seed=seed), er_allocation(n, K, r)


@pytest.fixture
def tracer():
    """Fresh enabled tracer installed as the process tracer for one test."""
    t = obs.Tracer(enabled=True)
    prev = obs.set_tracer(t)
    yield t
    obs.set_tracer(prev)


# ---- disabled path: hard no-op ------------------------------------------

def test_disabled_tracer_returns_null_span_singleton():
    t = obs.Tracer(enabled=False)
    a = t.span("phase.map", nnz=10)
    b = t.span("phase.reduce")
    assert a is b                          # one shared singleton, no alloc
    with a as sp:
        sp.set(bits=1)                     # all no-ops
    t.event("fault.crash", at=0)
    assert t.roots == []
    assert t.tree() == ()


def test_disabled_tracer_run_is_bitwise_noop():
    g, alloc = _case()
    prog = algo.pagerank()

    ref = engine.compile(prog, g, alloc, "coded", path="sparse").run(3)

    off = obs.Tracer(enabled=False)
    prev = obs.set_tracer(off)
    try:
        res = engine.compile(prog, g, alloc, "coded", path="sparse").run(3)
    finally:
        obs.set_tracer(prev)

    assert np.array_equal(res.state, ref.state)
    assert res.shuffle_bits == ref.shuffle_bits
    assert off.roots == []


# ---- enabled path: the pinned span tree ---------------------------------

PHASES = ("phase.map", "phase.encode", "phase.exchange", "phase.decode",
          "phase.reduce")


def test_pinned_span_tree_coded_run(tracer):
    g, alloc = _case()
    sess = engine.compile(algo.pagerank(), g, alloc, "coded", path="sparse")
    res = sess.run(3)

    iteration = ("engine.iteration", tuple((p, ()) for p in PHASES))
    assert tracer.tree() == (
        ("engine.compile", (("plan.compile", ()),)),
        ("plan.edge_tables", ()),
        ("engine.run", (iteration,) * 3),
    )

    # Span-attributed bits must equal the engine's own load accounting:
    # the exchange spans carry the Definition-2 numerator exactly.
    span_bits = sum(s.attrs["bits"] for s in tracer.find("phase.exchange"))
    assert span_bits == res.shuffle_bits
    assert res.normalized_load == span_bits / (g.n * g.n * T_BITS * res.iters)

    run_sp, = tracer.find("engine.run")
    assert run_sp.attrs["shuffle_bits"] == res.shuffle_bits
    for it, sp in enumerate(tracer.find("engine.iteration")):
        assert sp.attrs["iteration"] == it
        assert sp.duration_s > 0


def test_span_tree_with_faults_and_checkpoints(tracer, tmp_path):
    """Crash/recover boundaries and checkpoint epochs land in the tree."""
    g, alloc = _case(K=4, r=2)
    ck = SessionCheckpointer(str(tmp_path))
    sched = FaultSchedule([(1, "crash", (1,)), (2, "recover", (1,))])
    sess = engine.compile(algo.pagerank(), g, alloc, "coded", path="sparse")
    res = sess.run(3, checkpoint=ck, checkpoint_every=1, fault_schedule=sched)
    ck.wait()

    phases = tuple((p, ()) for p in PHASES)
    save = ("checkpoint.save", ())
    run_tree = next(r.tree() for r in tracer.roots if r.name == "engine.run")
    assert run_tree == ("engine.run", (
        ("engine.iteration", phases + (save,)),
        # crash boundary: the fault event, the in-place plan surgery, then
        # the repaired plan's edge tables
        ("engine.iteration",
         (("fault.crash", ()), ("plan.repair", ()), ("plan.edge_tables", ()))
         + phases + (save,)),
        # recovery boundary: back on the original compiled session
        ("engine.iteration", (("fault.recover", ()),) + phases + (save,)),
    ))

    # The actual writes happen on the checkpoint writer thread, so they are
    # separate roots (one per epoch), not children of checkpoint.save.
    writes = [r for r in tracer.roots if r.name == "checkpoint.write"]
    assert sorted(w.attrs["iteration"] for w in writes) == [1, 2, 3]
    assert all(w.thread != threading.current_thread().name for w in writes)

    crash, = tracer.find("fault.crash")
    assert crash.instant and crash.attrs["servers"] == "1"
    repair, = tracer.find("plan.repair")
    assert repair.attrs["failed"] == "1"
    assert repair.attrs["handover_bits"] > 0
    assert res.faults.crashes == 1 and res.faults.recoveries == 1


def test_chrome_trace_export_roundtrip(tracer, tmp_path):
    with tracer.span("engine.run", iters=1):
        with tracer.span("phase.exchange", bits=np.int64(96)):
            pass
        tracer.event("fault.crash", at=0)
    path = tracer.dump_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["engine.run"]["ph"] == "X"
    assert by_name["engine.run"]["dur"] >= by_name["phase.exchange"]["dur"]
    assert by_name["phase.exchange"]["args"]["bits"] == 96   # json-safe int
    assert by_name["fault.crash"]["ph"] == "i"
    assert by_name["thread_name"]["ph"] == "M"


def test_span_records_error_and_thread_nesting(tracer):
    with pytest.raises(ValueError):
        with tracer.span("engine.run"):
            raise ValueError("boom")
    sp, = tracer.find("engine.run")
    assert sp.attrs["error"] == "ValueError"

    # Spans opened on another thread nest on that thread's own stack.
    def worker():
        with tracer.span("other"):
            pass

    th = threading.Thread(target=worker, name="obs-worker")
    th.start()
    th.join()
    other, = tracer.find("other")
    assert other.thread == "obs-worker"
    assert other in tracer.roots           # not a child of the main thread


# ---- metrics ------------------------------------------------------------

def test_counter_and_gauge():
    reg = obs.MetricsRegistry()
    c = reg.counter("queries_total", help="admitted queries")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("inflight")
    g.set(5)
    g.dec(2)
    assert g.value == 3
    assert reg.counter("queries_total") is c   # created once, fetched after
    with pytest.raises(ValueError):
        reg.gauge("queries_total")             # type clash is an error


def test_histogram_quantiles_interpolate():
    h = obs.Histogram("lat", buckets=(0.1, 0.2, 0.4, 0.8))
    for v in (0.05, 0.15, 0.15, 0.3):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(0.65)
    # p50 = rank 2 of 4 -> second bucket (0.1, 0.2], both its obs covered
    assert 0.1 <= h.quantile(0.5) <= 0.2
    assert h.quantile(1.0) == pytest.approx(0.4)
    assert h.quantile(0.0) == 0.0
    ps = h.percentiles((50, 99))
    assert set(ps) == {"p50", "p99"}
    assert ps["p50"] <= ps["p99"]
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_prometheus_text_format():
    reg = obs.MetricsRegistry()
    reg.counter("serve_queries_total", help="admitted").inc(7)
    h = reg.histogram("serve_query_latency_seconds", buckets=(0.5, 1.0))
    h.observe(0.3)
    h.observe(0.7)
    text = reg.to_prometheus_text()
    assert "# TYPE serve_queries_total counter" in text
    assert "serve_queries_total 7" in text
    assert 'serve_query_latency_seconds_bucket{le="0.5"} 1' in text
    assert 'serve_query_latency_seconds_bucket{le="+Inf"} 2' in text
    assert "serve_query_latency_seconds_count 2" in text
    assert text.endswith("\n")


# ---- bench helpers ------------------------------------------------------

def test_measure_warmup_and_reps():
    calls = []

    def fn():
        calls.append(1)
        return len(calls)

    m = obs.measure(fn, reps=3, warmup=2)
    assert len(calls) == 5                 # 2 warmup + 3 timed
    assert m.result == 5                   # last rep's return value
    assert len(m.times_s) == 3
    assert m.best_s <= m.mean_s <= m.worst_s
    assert m.reduced_s("max") == m.worst_s
    with pytest.raises(ValueError):
        m.reduced_s("median")
    with pytest.raises(ValueError):
        obs.measure(fn, reps=0)


def test_measure_sync_and_memory():
    synced = []
    m = obs.measure(lambda: np.zeros(1 << 16), reps=2, warmup=0,
                    sync=synced.append, trace_memory=True)
    assert len(synced) == 2                # applied to every timed rep
    assert m.peak_bytes >= (1 << 16) * 8   # the float64 buffer was counted


def test_timeit_and_stopwatch():
    assert obs.timeit(lambda: None, reps=2, warmup=0) >= 0.0
    with obs.stopwatch() as sw:
        sum(range(1000))
    assert sw.s > 0
    assert sw.us == pytest.approx(sw.s * 1e6)
