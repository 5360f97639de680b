"""Blocked-dense SpMV Pallas TPU kernel.

The TPU adaptation of PageRank's Map+Reduce hot loop (DESIGN.md §3): the
adjacency is consumed as MXU-aligned dense tiles streamed HBM->VMEM; each grid
step contracts one [bm, bk] tile against a [bk, 1] slice of the source vector
and accumulates into the [bm, 1] output block, which stays resident in VMEM
across the k-sweep (revisiting output blocks is the standard Pallas matmul
accumulation pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import interpret_mode


def _spmv_kernel(a_ref, x_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(a, x, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def spmv_pallas(adj: jnp.ndarray, x: jnp.ndarray, *, bm: int = 128,
                bk: int = 128, interpret: bool | None = None) -> jnp.ndarray:
    """y = adj @ x via pallas_call. Shapes must tile evenly by (bm, bk)."""
    m, n = adj.shape
    assert m % bm == 0 and n % bk == 0, (m, n, bm, bk)
    x2 = x.reshape(n, 1)
    out = pl.pallas_call(
        _spmv_kernel,
        grid=(m // bm, n // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bk, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        interpret=interpret_mode(interpret),
    )(adj, x2)
    return out.reshape(m)
