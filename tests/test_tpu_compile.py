"""Compile-only checks of the main path's kernels for a described TPU v5e.

Nothing runs. Each test lowers and compiles for a `v5e:2x2` topology that
is described, not attached, and asserts that the Mosaic kernel is in the
compiled program (`tpu_custom_call`): interpret-mode tests cannot see what
the chip's compiler refuses. The topology is described inside a fixture,
never at import, so every pytest worker collects the same tests and only a
worker that runs them loads the TPU compiler. Under `-n N --dist loadfile`
that is one worker; where several workers may load it at once (`--dist
load`), set ALLOW_MULTIPLE_LIBTPU_LOAD=1. A failure to describe the
topology fails the tests; only a missing libtpu skips them.
"""
import importlib.util
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro import graphs
from repro.core.allocation import divisible_n, er_allocation
from repro.core.fused_shuffle import FusedSparseShuffle
from repro.core.shuffle_plan import compile_hierarchical, compile_plan_csr
from repro.kernels.spmv.spmv import spmv_pallas
from repro.kernels.xor_code import ops as xor_ops
from repro.kernels.xor_code.xor_code import xor_encode_pallas
from repro.launch.mesh import Topology


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler here")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # A described compile is written to the persistent cache but can never
    # be read back without a chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("r", [2, 3])
def test_xor_encode_pallas_compiles(one_chip, r):
    rows = _shape((r, 4096, 128), jnp.uint32, one_chip)
    valid = _shape((r, 4096), jnp.bool_, one_chip)
    fn = jax.jit(lambda a, v: xor_encode_pallas(a, v, interpret=False))
    _assert_kernel(fn.lower(rows, valid).compile())


def test_xor_encode_slots_compiles_at_server_width(one_chip):
    """One server's fused-path encode at a real per-server width: K=4, r=2
    on an ER graph of 2**17 vertices at average degree 32."""
    W, Lmax, r = 525_406, 2_098_825, 2
    args = (_shape((Lmax + 1,), jnp.uint32, one_chip),
            _shape((W, r), jnp.int32, one_chip),
            _shape((W, r), jnp.uint32, one_chip),
            _shape((W, r), jnp.uint32, one_chip))
    fn = jax.jit(lambda *a: xor_ops.xor_encode_slots(*a, interpret=False))
    _assert_kernel(fn.lower(*args).compile())


def test_spmv_pallas_compiles(one_chip):
    adj = _shape((128, 262144), jnp.float32, one_chip)
    x = _shape((262144,), jnp.float32, one_chip)
    fn = jax.jit(lambda a, b: spmv_pallas(a, b, interpret=False))
    _assert_kernel(fn.lower(adj, x).compile())


@pytest.mark.parametrize("topology", [None, Topology(2, 2)],
                         ids=["flat", "racks2x2"])
def test_fused_exchange_compiles_on_four_chips(topo, topology):
    """The 4-device exchange with the kernel encode, on a mesh of the
    described chips; the kernel route is chosen from the mesh's platform."""
    K, r = 4, 2
    n = divisible_n(2000, K, r)
    g = graphs.erdos_renyi(n, 16 / n, seed=1)
    alloc = er_allocation(n, K, r)
    devs = np.asarray(topo.devices)
    if topology is None:
        plan = compile_plan_csr(g.csr, alloc)
        mesh = Mesh(devs, ("servers",))
    else:
        plan = compile_hierarchical(g.csr, alloc, topology)
        mesh = Mesh(devs.reshape(2, 2), ("racks", "servers"))
    ex = FusedSparseShuffle(plan, g.csr, alloc, mesh, encode="xor-kernel")
    arrays = ((jax.ShapeDtypeStruct(ex.operand_shape(), jnp.uint32),)
              + ex._host_tables())
    args = [_shape(a.shape, a.dtype, NamedSharding(mesh, s))
            for a, s in zip(arrays, ex._in_specs())]
    compiled = ex._fn.lower(*args).compile()
    _assert_kernel(compiled)
    # The compiler may lower a small all_gather to an all-reduce of
    # zero-padded buffers; either way the exchange crosses the chips.
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text
    # the exchange's named scopes survive into the chip's HLO metadata,
    # where the profiler reads them
    for scope in ("exchange.encode", "exchange.gather", "exchange.decode"):
        assert f"/{scope}/" in text
    if topology is None:
        # The host lays the flat operand in slot order, so the only gather
        # left reads the deliveries from the all_gathered buffers.
        gathers = [line for line in text.splitlines()
                   if re.search(r"= \S+ gather\(", line)]
        assert len(gathers) == 1, gathers
        assert "/exchange.encode/" not in gathers[0]
