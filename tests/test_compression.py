"""Gradient compression: exactness of the wire primitive on one device and
convergence parity + bandwidth accounting on a real 4-device mesh
(subprocess so the host-device flag stays contained)."""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp

from repro.train.compression import dequantize, quantize, wire_bytes


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal(1000) * 5, jnp.float32)
    q, s = quantize(x)
    err = np.abs(np.asarray(dequantize(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-7          # half-ULP of the grid
    assert q.dtype == jnp.int8


def test_wire_bytes_accounting():
    params = {"a": jnp.zeros((10, 10)), "b": jnp.zeros(50)}
    assert wire_bytes(params, compressed=False) == 150 * 4
    assert wire_bytes(params, compressed=True) == 150


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.train.compression import compressed_psum_mean, ef_compress_tree, ef_state

def shard_map(f, **kw):
    return jax.shard_map(f, check_vma=False, **kw)

mesh = jax.make_mesh((4,), ("dp",))

# 1. wire primitive: compressed mean-psum ~= exact mean.
x = jnp.arange(16, dtype=jnp.float32).reshape(4, 4) / 7.0

def f(xs):
    return compressed_psum_mean(xs, "dp")

got = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
want = jnp.broadcast_to(x.reshape(4, 1, 4).mean(0), (4, 4)).reshape(4,4)
err1 = float(jnp.abs(got - want).max())

# 2. convergence parity: least squares with per-shard data, EF-compressed DP.
rng = np.random.default_rng(1)
A = jnp.array(rng.standard_normal((64, 8)), jnp.float32)
wstar = jnp.array(rng.standard_normal(8), jnp.float32)
y = A @ wstar

def loss(w, a, b):
    r = a @ w - b
    return 0.5 * jnp.mean(r * r)

def train(compressed):
    w = jnp.zeros(8)
    res = ef_state({"w": w})

    def step(w, res, a, b):
        def shard_step(ws, rs, ash, bsh):
            g = jax.grad(loss)(ws, ash, bsh)
            if compressed:
                red, new_r = ef_compress_tree({"w": g}, rs, "dp")
                return red["w"], new_r
            return jax.lax.pmean(g, "dp"), rs
        f = shard_map(shard_step, mesh=mesh,
                      in_specs=(P(), {"w": P()}, P("dp"), P("dp")),
                      out_specs=(P(), {"w": P()}))
        g, new_res = f(w, res, a, b)
        return w - 0.05 * g, new_res

    stepj = jax.jit(step)
    for _ in range(400):
        w, res = stepj(w, res, A, y)
    return float(loss(w, A, y))

l_exact = train(False)
l_comp = train(True)
print(json.dumps({"err1": err1, "l_exact": l_exact, "l_comp": l_comp}))
"""


def test_compressed_dp_converges_on_mesh():
    proc = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                          text=True, timeout=420,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                               "HOME": os.environ.get("HOME", "/tmp"),
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["err1"] < 0.02                      # int8 grid error
    assert res["l_exact"] < 1e-3
    # Error feedback keeps compressed training within striking distance.
    assert res["l_comp"] < 5e-2, res