"""XOR alignment-table packing as a Pallas TPU kernel.

The coded Shuffle's encode is a masked XOR-reduce over the r table rows
(paper Fig. 6). On TPU this is a VPU bitwise op over [bc, W] uint32 tiles in
VMEM; r is small and static, so the row loop is unrolled. The same kernel
serves decode (strip) because XOR is its own inverse.

Validity enters the kernel as a uint32 keep-mask of shape [r, C, 1] (all
ones or all zeros per row), which broadcasts across the lane axis. Mosaic
refuses a bool block and the [bc] -> [bc, 1] reshape a [r, C] mask would
need (`infer-vector-layout: unsupported shape cast`). A call without a
mask (every slot valid, the batched column route) reads no mask at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import interpret_mode


def _xor_kernel(*refs, r: int, masked: bool):
    rows_ref, o_ref = refs[0], refs[-1]
    acc = None
    for i in range(r):                       # r is static: unrolled on the VPU
        seg = rows_ref[i]
        if masked:
            seg = seg & refs[1][i]           # [bc, W] & [bc, 1] keep-mask
        acc = seg if acc is None else jnp.bitwise_xor(acc, seg)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bc", "interpret"))
def xor_encode_pallas(rows: jnp.ndarray, valid: jnp.ndarray | None = None, *,
                      bc: int = 256,
                      interpret: bool | None = None) -> jnp.ndarray:
    """rows [r, C, W] uint32, valid [r, C] bool -> coded [C, W] uint32.

    `valid=None` means every row of every column is valid. `interpret`
    defaults to the platform's mode (`kernels.platform.interpret_mode`).
    """
    r, c, w = rows.shape
    pad = (-c) % bc
    operands = [jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))]
    in_specs = [pl.BlockSpec((r, bc, w), lambda i: (0, i, 0))]
    if valid is not None:
        keep = jnp.where(valid, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        operands.append(jnp.pad(keep, ((0, 0), (0, pad)))[..., None])
        in_specs.append(pl.BlockSpec((r, bc, 1), lambda i: (0, i, 0)))
    cp = c + pad
    out = pl.pallas_call(
        functools.partial(_xor_kernel, r=r, masked=valid is not None),
        grid=(cp // bc,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bc, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, w), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(*operands)
    return out[:c]
