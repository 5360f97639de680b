"""The trace reduction, on a small trace recorded on the chip, and the
loader, on a trace recorded here."""
import json

import numpy as np
import pytest

from bench import harness, tracereduce
from conftest import ROOT

RECORDED = ROOT / "bench/tests/data/trace_er20_k4r2_2iters.json"


@pytest.fixture(scope="module")
def recorded():
    events = json.loads(RECORDED.read_text())
    (win,) = [e for e in events["host"] if e[0] == "bench.window"]
    return events, win[1], win[2]


@pytest.fixture(scope="module")
def reduced(recorded):
    events, t0, t1 = recorded
    return tracereduce.reduce(events, t0, t1)


def _busy_by_grid(ops, t0, t1, step_ns=100.0):
    """Busy time by marking a 100 ns grid: an independent union."""
    grid = np.zeros(int((t1 - t0) / step_ns) + 1, bool)
    for _, a, b in ops:
        grid[int((max(a, t0) - t0) / step_ns):int((min(b, t1) - t0) / step_ns)] = True
    return grid.sum() * step_ns / 1e9


def test_busy_and_idle_add_up_to_the_window(recorded, reduced):
    events, t0, t1 = recorded
    assert reduced["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert sorted(ch["id"] for ch in reduced["chips"]) == [0, 1, 2, 3]
    for ch in reduced["chips"]:
        ops = events["devices"][str(ch["id"])]["ops"]
        assert ch["busy_s"] == pytest.approx(_busy_by_grid(ops, t0, t1),
                                             abs=2e-5)
        assert ch["busy_s"] + sum(ch["idle"].values()) == pytest.approx(
            reduced["window_s"], rel=1e-9)
        # busy time is a union: no more than the op time summed
        assert ch["busy_s"] <= sum(ch["ops"].values()) + 1e-12


def test_idle_is_split_by_the_host_span_open(recorded, reduced):
    events, _, _ = recorded
    names = {e[0] for e in events["host"]}
    for ch in reduced["chips"]:
        assert set(ch["idle"]) <= names | {"none"}
        # the host's Map runs while the device has nothing to do
        assert ch["idle"]["phase.map"] > 0.5
        assert ch["idle"].get("phase.exchange", 0.0) < 0.1


def test_device_metric_readers(reduced):
    rec = {"iters": 2, "trace": reduced}
    exchange = harness.metric_reader("exchange_device_s")(rec)
    collective = harness.metric_reader("collective_device_s")(rec)
    idle = harness.metric_reader("device_idle_share")(rec)
    # two jit_per_server modules per chip, ~0.22 s each at this size
    assert 0.1 < exchange < 0.5
    slowest = max(sum(ch["modules"].values()) for ch in reduced["chips"]) / 2
    assert exchange == pytest.approx(slowest)
    # the all-reduce alone, not the ops that merely read its result
    for ch in reduced["chips"]:
        hits = [k for k in ch["ops"] if k.startswith("%all-reduce")]
        assert len(hits) == 1
    assert 0 < collective < exchange
    assert 50 < idle < 100


def test_readers_return_nothing_without_a_trace():
    rec = {"iters": 2, "trace": None, "spans": {}, "setup": {}}
    for name in ("exchange_device_s", "collective_device_s",
                 "device_idle_share", "map_s", "reduce_s",
                 "exchange_host_s", "plan_compile_s", "partition_s"):
        assert harness.metric_reader(name)(rec) is None


def test_top_averages_over_chips_and_keeps_ten(reduced):
    top = tracereduce.top([ch["ops"] for ch in reduced["chips"]])
    assert len(top) == 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    name, s = top[0]
    assert s == pytest.approx(np.mean([ch["ops"].get(name, 0.0)
                                       for ch in reduced["chips"]]))


def test_op_names_drop_layouts():
    text = ("%fusion.1 = u32[8392336]{0:T(1024)} fusion(u32[4,4199905]"
            "{1,0:T(4,128)S(1)} %pad.15), kind=kCustom")
    assert tracereduce.op_name(text) == (
        "%fusion.1 = u32[8392336] fusion(u32[4,4199905] %pad.15), "
        "kind=kCustom")


def test_extract_reads_host_spans_of_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("phase.map"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tracereduce.extract(tracereduce.find_xplane(tmp_path),
                                 {"bench.window", "phase.map"})
    (win,) = [e for e in events["host"] if e[0] == "bench.window"]
    (mp,) = [e for e in events["host"] if e[0] == "phase.map"]
    assert win[1] <= mp[1] <= mp[2] <= win[2]
    assert events["devices"] == {}          # no TPU here
