"""Drives the benchmark's whole run, minus the look for a chip, at a tiny
size on four virtual CPU devices, with the timed path sound and broken.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/cpu_cell.py <workload> <scale> <out.json>

Writes {case: result object} for the sound run (plain and traced), for
each fault planted under the timed path, and the control's readings.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time
from unittest import mock

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from repro.core.bitcodec import floats_to_words, words_to_floats  # noqa: E402
from repro.core.engine import CompiledEngine  # noqa: E402
from repro.core.fused_shuffle import FusedSparseShuffle  # noqa: E402

SEED = 2**31 + 11
_exchange = FusedSparseShuffle.exchange_words


def _zero_half(self, words):
    out = _exchange(self, words).copy()
    out[out.shape[0] // 2:] = 0
    return out


def _alter_one(self, words):
    out = _exchange(self, words).copy()
    i = out.shape[0] // 3                           # one delivered value x2
    out[i] = floats_to_words(2 * words_to_floats(out[i:i + 1]))[0]
    return out


FAULTS = {
    "state_unchanged": (CompiledEngine, "_step", lambda self, s: (s, 0)),
    "exchange_left_out": (FusedSparseShuffle, "exchange_words",
                          lambda self, w: np.zeros_like(_exchange(self, w))),
    "half_deliveries_left_out": (FusedSparseShuffle, "exchange_words",
                                 _zero_half),
    "answer_altered": (FusedSparseShuffle, "exchange_words", _alter_one),
}


def tiny_spec(workload: str, scale: int, tmp: pathlib.Path) -> dict:
    spec = harness.load_spec()
    w = {x["name"]: x for x in spec["workloads"]}[workload]
    for cfg in spec["configs"]:
        if cfg["name"] == w["config"]:
            body = json.loads((ROOT / cfg["file"]).read_text())
            body["scale"] = scale
            path = tmp / f"{cfg['name']}.json"
            path.write_text(json.dumps(body))
            cfg["file"] = str(path)
    return spec


def main(workload: str, scale: int, out: str) -> None:
    spec = tiny_spec(workload, scale, pathlib.Path(out).parent)

    def run(trace=False):
        return harness.run_cell(spec, workload, SEED, 0.3, trace,
                                t_start=time.perf_counter(),
                                require_tpu=False)

    results = {"sound": run(), "sound_traced": run(trace=True)}
    for name, (cls, attr, fake) in FAULTS.items():
        with mock.patch.object(cls, attr, fake):
            results[name] = run()
    c = harness.cell(spec, workload)
    results["control"] = harness.control_readings(c, SEED, 6)
    pathlib.Path(out).write_text(json.dumps(results))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), sys.argv[3])
