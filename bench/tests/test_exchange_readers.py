"""The readers of the fused exchange's own instrumentation: set-up spans,
registry counters, and device time under the exchange's named scopes."""
import json

import pytest

from bench import harness, scopes, tracereduce
from conftest import ROOT
from repro import obs

RECORDED = ROOT / "bench/tests/data/scopes_graph500_18_2iters.json"

SPAN_READERS = {"edge_tables_s": "span:plan.edge_tables",
                "exchange_first_call_s": "span:exchange.first_call"}
COUNTERS = ("exchange_calls_total", "exchange_host_copy_bytes_total",
            "exchange_bits_useful_total", "exchange_bits_sent_total")
NEW = ("edge_tables_s", "exchange_first_call_s", "encode_device_s",
       "decode_device_s", "host_copy_bytes", "exchange_useful_share")


def _rec(trace=None, setup=None, iters=4):
    return {"iters": iters, "spans": {}, "setup": setup or {}, "trace": trace}


@pytest.fixture
def registry():
    reg = obs.MetricsRegistry()
    prev = obs.set_registry(reg)
    yield reg
    obs.set_registry(prev)


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_readers_read_their_set_up_span(name):
    read = harness.metric_reader(name)
    assert read(_rec(setup={SPAN_READERS[name]: 2.5, "partition": 9.0})) == 2.5
    assert read(_rec(setup={"partition": 9.0})) is None


def test_counter_readers_give_per_call_bytes_and_useful_share(registry):
    # the cell's counts (K=4, Lmax+1=3,860,438, Dmax=989,729, W=989,137,
    # 3,873,457 coded columns of 16 bits), over 3 calls
    calls, per_call = 3, 4 * 4 * (3_860_438 + 989_729)
    registry.counter(COUNTERS[0]).inc(calls)
    registry.counter(COUNTERS[1]).inc(calls * per_call)
    registry.counter(COUNTERS[2]).inc(calls * 3_873_457 * 16)
    registry.counter(COUNTERS[3]).inc(calls * 32 * 4 * 989_137)
    rec = _rec()
    assert harness.metric_reader("host_copy_bytes")(rec) == 77_602_672
    share = harness.metric_reader("exchange_useful_share")(rec)
    assert share == pytest.approx(48.95, abs=0.01)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_for_a_program_without_them(registry, name):
    # no counters, no set-up spans, no device trace: None, never 0
    assert harness.metric_reader(name)(_rec()) is None


@pytest.mark.parametrize("op_name,scope", [
    ("jit(per_server)/shard_map/exchange.decode/gather", "exchange.decode"),
    ("jit(per_server)/shard_map/exchange.encode/jit(xor_encode_pallas)/"
     "pallas_call", "exchange.encode"),
    ("exchange.gather/all_gather", "exchange.gather"),
    ("exchange.decode/or", "exchange.decode"),
    ("jit(per_server)/shard_map/my_exchange.decoder/gather", None),
    ("", None),
])
def test_scope_of_reads_a_name_stack(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_assign_maps_ops_through_their_module():
    device = {
        "modules": [["jit_per_server(7)", 100, 200],
                    ["jit_other(8)", 300, 400]],
        "ops": [["%fusion.1 = u32[8] fusion(%pad.15)", 110, 150],
                ["%all-reduce.3 = u32[8] all-reduce(%x)", 150, 160],
                ["%fusion.1 = u32[4] fusion(%y)", 310, 320],   # other module
                ["%fusion = u32[4] fusion(%z)", 500, 510]]}    # no module
    table = {"jit_per_server(7)": {"fusion.1": "exchange.decode",
                                   "fusion": "exchange.encode"},
             "jit_other": {}}
    assert scopes.assign(device, table) == [["exchange.decode", 110, 150]]
    # a module named only by its bare name is found too
    bare = {"jit_per_server": table["jit_per_server(7)"]}
    assert scopes.assign(device, bare) == [["exchange.decode", 110, 150]]


def test_seconds_clips_to_the_window_and_splits_by_scope():
    events = {"window": [100, 1100], "devices": {
        "0": [["exchange.encode", 50, 150],      # half inside
              ["exchange.decode", 200, 600],
              ["exchange.decode", 1000, 1200]],  # half inside
        "1": [["exchange.gather", 300, 400]]}}
    got = scopes.seconds(events)
    assert got[0] == pytest.approx({"exchange.encode": 50e-9,
                                    "exchange.gather": 0.0,
                                    "exchange.decode": 500e-9})
    assert got[1]["exchange.gather"] == pytest.approx(100e-9)
    assert scopes.seconds({"window": None, "devices": {}}) == []


def test_device_seconds_is_the_slowest_chip_per_iteration(monkeypatch,
                                                           tmp_path):
    xplane = tmp_path / "t.xplane.pb"
    xplane.write_bytes(b"")
    monkeypatch.setattr(tracereduce, "find_xplane", lambda d: xplane)
    monkeypatch.setattr(scopes, "extract", lambda path: {
        "window": [0, 10_000], "devices": {
            "0": [["exchange.encode", 0, 4000]],
            "1": [["exchange.encode", 0, 6000]]}})
    scopes._traced_run.cache_clear()
    rec = _rec(trace={"chips": []}, iters=2)
    try:
        assert scopes.device_seconds(rec, "exchange.encode") == \
            pytest.approx(3e-6)
        assert scopes.device_seconds(rec, "exchange.decode") is None
    finally:
        scopes._traced_run.cache_clear()


def test_extract_reads_the_scopes_of_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("exchange.encode"):
            y = x[::2] << 3
        with jax.named_scope("exchange.decode"):
            return (y ^ 7).sum()

    x = jnp.arange(1024, dtype=jnp.uint32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(scopes.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tracereduce.find_xplane(tmp_path)
    by_module = scopes.hlo_scopes(path.read_bytes())
    (name,) = [k for k in by_module if k.startswith("jit_f(")]
    assert set(by_module[name].values()) == {"exchange.encode",
                                             "exchange.decode"}
    assert by_module["jit_f"] is by_module[name]
    events = scopes.extract(path)
    assert events["devices"] == {}          # no TPU here
    t0, t1 = events["window"]
    assert 0 < t0 < t1


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def _scoped_seconds(recorded):
    return scopes.seconds({"window": recorded["window"], "devices": {
        cid: scopes.assign(dev, recorded["hlo_scopes"])
        for cid, dev in recorded["devices"].items()}})


def test_recorded_chip_ops_split_by_scope(recorded):
    (table,) = recorded["hlo_scopes"].values()
    # the three random gathers: encode's from loc, decode's from the
    # gathered buffers, the strip from loc
    assert table["fusion"] == "exchange.encode"
    assert table["fusion.1"] == table["fusion.2"] == "exchange.decode"
    assert table["xor_encode_pallas.1"] == "exchange.encode"
    # the all-reduce the all_gather lowers to comes after the scopes
    assert "all-reduce.3" not in table
    chips = _scoped_seconds(recorded)
    assert len(chips) == 4
    for ch in chips:                        # two iterations in the extract
        assert 0.012 < ch["exchange.encode"] / 2 < 0.017
        assert 0.028 < ch["exchange.decode"] / 2 < 0.040
        assert ch["exchange.gather"] < 0.001


def test_recorded_scopes_and_collective_cover_the_exchange(recorded):
    events = {"devices": recorded["devices"], "host": []}
    reduced = tracereduce.reduce(events, *recorded["window"])
    chips = _scoped_seconds(recorded)
    for ch in reduced["chips"]:
        sc = chips[ch["id"]]
        module = sum(ch["modules"].values())
        collective = sum(s for k, s in ch["ops"].items()
                         if k.startswith("%all-reduce"))
        covered = sc["exchange.encode"] + sc["exchange.decode"] + collective
        assert 0.9 * module <= covered <= module
