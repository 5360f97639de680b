"""The cell's session at a tiny size on four virtual CPU devices: sound
runs agree with the plain reference, and every fault planted under the
timed path, and the lower-precision control, are judged not correct."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

WORKLOAD = "graph500-18-k4r2.pagerank"


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    out = tmp_path_factory.mktemp("cell") / "results.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    subprocess.run([sys.executable, str(ROOT / "bench/tests/cpu_cell.py"),
                    WORKLOAD, "10", str(out)],
                   check=True, env=env, timeout=600)
    return json.loads(out.read_text())


@pytest.mark.parametrize("case", ["sound", "sound_traced"])
def test_sound_run_is_correct(results, case):
    res = results[case]
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["count"] == 4
    for check in res["checks"].values():
        assert check["value"] <= check["limit"]
    assert list(res)[-1] == "checks"


def test_sound_run_reports_end_to_end_metrics(results):
    assert set(results["sound"]["metrics"]) == {"iter_s", "setup_s"}
    assert all(m["value"] > 0 for m in results["sound"]["metrics"].values())


def test_traced_run_reports_host_span_metrics(results):
    got = results["sound_traced"]["metrics"]
    for name in ("plan_compile_s", "partition_s", "map_s", "reduce_s",
                 "exchange_host_s"):
        assert got[name]["value"] > 0
    # no device trace on the CPU: device metrics are left out, never 0
    for name in ("exchange_device_s", "collective_device_s",
                 "device_idle_share"):
        assert name not in got


@pytest.mark.parametrize("fault", ["state_unchanged", "exchange_left_out",
                                   "half_deliveries_left_out",
                                   "answer_altered"])
def test_fault_under_the_timed_path_is_not_correct(results, fault):
    assert results[fault]["correct"] is False


def test_lower_precision_control_is_not_correct(results):
    ctl = results["control"]
    assert any(ctl["control"][k] > lim for k, lim in ctl["limits"].items())
