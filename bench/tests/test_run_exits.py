"""`bench/run.py` exits non-zero and prints no result without a TPU, and
in a directory that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

ARGS = ["--workload", "graph500-18-k4r2.pagerank", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(root / "bench/run.py"), *ARGS],
                          capture_output=True, text=True, env=env,
                          cwd=root, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    return True


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
