"""Configurations, graph families, traffic mixes, programs and per-layer
metrics are found by file name: adding one is adding a file and an entry,
with no edit to a file that exists."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from conftest import ROOT

CELL = "graph500-18-k4r2.pagerank"
NEW_CELL = "ring12-rack22.pagerank-d02"

# a graph family that no file of the benchmark has: a ring with chords
RING = '''
import numpy as np


def generate(cfg, seed):
    n = cfg["n"]
    i = np.arange(n)
    nbrs = np.sort(np.stack([(i - 1) % n, (i + 1) % n,
                             (i + cfg["chord"] + seed % 3) % n]), axis=0)
    rows = np.repeat(i, 3)
    keys = np.unique(np.concatenate([rows * n + nbrs.T.ravel(),
                                     nbrs.T.ravel() * n + rows]))
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(keys // n, minlength=n), out=indptr[1:])
    return indptr, (keys % n).astype(np.int32)
'''


@pytest.fixture
def root(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _add_files(root):
    """A configuration with a new graph family and a two-level topology, a
    traffic mix with its own program, and a metric: files and entries
    only."""
    spec = harness.load_spec(root)
    (root / "bench/graphs/ring.py").write_text(RING)
    (root / "bench/configs/ring12-rack22.json").write_text(json.dumps({
        "name": "ring12-rack22", "graph": "ring", "n": 600, "chord": 97,
        "graph_seed": 4,
        "r": 2, "topology": {"racks": 2, "servers_per_rack": 2},
        "encode": "xor-kernel", "value_dtype": "float32", "reduced": {}}))
    traffic = json.loads((root / "bench/traffic/pagerank.json").read_text())
    traffic.update(program="pagerank_copy", params={"damping": 0.2})
    (root / "bench/traffic/pagerank-d02.json").write_text(json.dumps(traffic))
    shutil.copy(root / "bench/programs/pagerank.py",
                root / "bench/programs/pagerank_copy.py")
    (root / "bench/metrics/iters_traced.py").write_text(
        "def read(rec):\n    return float(rec['iters'])\n")
    spec["configs"].append({"name": "ring12-rack22", "source": "x",
                            "file": "bench/configs/ring12-rack22.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": NEW_CELL, "config": "ring12-rack22",
                              "traffic": "pagerank-d02", "chips": 4,
                              "why": "x"})
    spec["per_layer"].append({"name": "iters_traced", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "iter_s",
                              "workloads": [NEW_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.load_spec(root)


def test_cell_resolves_its_files(root):
    c = harness.cell(harness.load_spec(root), CELL, root)
    assert harness.topology(c["config"]).K == 4 and c["config"]["r"] == 2
    assert c["graph"].__file__.endswith("graph500.py")
    assert c["traffic"]["program"] == "pagerank"
    assert callable(c["program"].reference)


def test_new_config_family_topology_traffic_program_and_metric_by_name(root):
    spec = _add_files(root)
    c = harness.cell(spec, NEW_CELL, root)
    topo = harness.topology(c["config"])
    assert (topo.racks, topo.servers_per_rack) == (2, 2)
    assert c["graph"].__file__.endswith("ring.py")
    assert c["traffic"]["params"] == {"damping": 0.2}
    assert c["program"].__file__.endswith("pagerank_copy.py")
    indptr, _ = harness.make_graph(c, 5)
    assert (indptr.size - 1) % 12 == 0       # padded for the allocation
    assert harness.metric_reader("iters_traced", root)({"iters": 3}) == 3.0
    names = [m["name"] for m in
             harness.cell_metrics(spec, NEW_CELL, "per_layer")]
    assert names == ["iters_traced"]
    # the existing cell does not pick up the new cell's metric
    assert "iters_traced" not in [
        m["name"] for m in harness.cell_metrics(spec, CELL, "per_layer")]


def test_new_cell_runs_from_its_files_alone(root):
    """The added cell runs end to end, minus the look for a chip, on four
    virtual CPU devices: the two-level exchange, the new graph family and
    program, judged correct against the plain reference."""
    _add_files(root)
    code = (
        "import json, pathlib, sys, time\n"
        f"sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]\n"
        "from bench import harness\n"
        f"root = pathlib.Path({str(root)!r})\n"
        "res = harness.run_cell(harness.load_spec(root), "
        f"{NEW_CELL!r}, 2**31 + 3, 0.2, False, t_start=time.perf_counter(), "
        "root=root, require_tpu=False)\n"
        "print(json.dumps(res))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.splitlines()[-1])
    assert res["correct"] is True and res["attempted"] >= 1
    assert "Topology(racks=2, servers_per_rack=2)" in p.stderr


def test_unknown_workload_and_missing_files_raise(root):
    spec = harness.load_spec(root)
    with pytest.raises(KeyError):
        harness.cell(spec, "nope", root)
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("nope", root)
