"""Jitted public wrappers for XOR encode/decode (fused TPU shuffle path).

`interpret=None` everywhere below: the platform decides
(`kernels.platform.interpret_mode`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .xor_code import xor_encode_pallas


def xor_encode(rows: jnp.ndarray, valid: jnp.ndarray | None, *,
               use_kernel: bool = True,
               interpret: bool | None = None) -> jnp.ndarray:
    if use_kernel:
        return xor_encode_pallas(rows, valid, interpret=interpret)
    if valid is None:
        valid = jnp.ones(rows.shape[:2], dtype=jnp.bool_)
    return ref.xor_encode(rows, valid)


def xor_decode(coded: jnp.ndarray, known_rows: jnp.ndarray,
               known_valid: jnp.ndarray, *, use_kernel: bool = True,
               interpret: bool | None = None) -> jnp.ndarray:
    """coded [C, W]; known_rows [r-1, C, W]; -> missing segments [C, W]."""
    strip = xor_encode(known_rows, known_valid, use_kernel=use_kernel,
                       interpret=interpret)
    return jnp.bitwise_xor(coded, strip)


def xor_encode_rows(rows: jnp.ndarray, *, lanes: int = 128,
                    use_kernel: bool = True,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Slot-major XOR fold: [r, N] uint32 pre-masked slot words -> [N].

    Invalid slots are zero, so no validity mask is needed and the column
    axis can be reshaped freely: it is folded into [r, N/lanes, lanes] so
    the Pallas kernel sees VPU-shaped uint32 tiles (lane dim 128). Every
    route below ends here.
    """
    r, c = rows.shape
    if c == 0:                     # empty schedule: nothing to multicast
        return jnp.zeros(0, jnp.uint32)
    pad = (-c) % lanes
    rows = jnp.pad(rows, ((0, 0), (0, pad)))
    rows = rows.reshape(r, (c + pad) // lanes, lanes)
    out = xor_encode(rows, None, use_kernel=use_kernel, interpret=interpret)
    return out.reshape(-1)[:c]


def xor_encode_columns(slot_words, *, lanes: int = 128,
                       use_kernel: bool = True,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Batched ShufflePlan route: [C, r] uint32 slot words -> [C] coded columns.

    The plan executor hands over one pre-masked segment word per (column,
    slot); the slot axis is moved first and folded by `xor_encode_rows` -
    this is the path that feeds the kernel realistic workloads (C ~
    thousands of coded columns per Shuffle).

    Batched-payload route: [C, r, B] slot words (B query payloads per slot,
    the multi-query Shuffle) fold the payload axis into the column axis -
    XOR is elementwise, so the C*B fold is free - and return [C, B] coded
    columns; payload column b is bitwise the single-payload encode of its
    slice.
    """
    slot_words = jnp.asarray(slot_words, jnp.uint32)
    r = slot_words.shape[1]
    rows = jnp.moveaxis(slot_words, 1, 0)              # [r, C(, B)]
    out = xor_encode_rows(rows.reshape(r, -1), lanes=lanes,
                          use_kernel=use_kernel, interpret=interpret)
    return out.reshape(rows.shape[1:])


def xor_strip_columns(slot_words, *, lanes: int = 128,
                      use_kernel: bool = True,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Per-slot strip words: [C, r] with strip[:, t] = XOR of the OTHER slots.

    This is the receiver side of the coded Shuffle: the receiver at slot t
    XORs the locally-recomputable slots out of the coded column, leaving its
    own segment (`coded ^ strip[:, t]`). r is small and static, so the
    per-slot loop unrolls into r batched kernel calls. Batched payloads
    [C, r, B] -> [C, r, B] strips via the same per-slot loop (the slot axis
    is axis 1 in both layouts).
    """
    slot_words = jnp.asarray(slot_words, jnp.uint32)
    _, r = slot_words.shape[:2]
    cols = []
    for t in range(r):
        others = slot_words.at[:, t].set(jnp.uint32(0))
        cols.append(xor_encode_columns(others, lanes=lanes,
                                       use_kernel=use_kernel,
                                       interpret=interpret))
    return jnp.stack(cols, axis=1)


def xor_encode_slots(loc: jnp.ndarray, idx: jnp.ndarray, shift: jnp.ndarray,
                     mask: jnp.ndarray, *, lanes: int = 128,
                     use_kernel: bool = True,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Per-shard encode: one server's packed coded buffer.

    Gathers the server's slot words from its local value vector, aligns each
    segment (left-shift + keep-mask, zero for sentinel slots), then XOR-folds
    the r slots through the kernel's column route.

    loc [L+1] uint32 local words (last entry 0 = sentinel); idx [W, r] int
    into loc; shift/mask [W, r] uint32 -> [W] uint32 coded columns.
    Batched loc [L+1, B] (B payload words per local value) gathers to
    [W, r, B], the shift/mask tables broadcast behind the payload axis, and
    the batched-column route returns [W, B] coded columns. The fused
    exchange stores these tables long axis last and folds through
    `xor_encode_rows` itself (`core.fused_shuffle.shard_encoder`).
    """
    gathered = loc[idx]
    if gathered.ndim == 3:
        shift, mask = shift[..., None], mask[..., None]
    slotw = (gathered << shift) & mask
    return xor_encode_columns(slotw, lanes=lanes, use_kernel=use_kernel,
                              interpret=interpret)


def floats_as_words(x: jnp.ndarray) -> jnp.ndarray:
    """Bit-preserving float32 -> uint32 view (lane codec for the fused path)."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)


def words_as_floats(w: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(w.astype(jnp.uint32), jnp.float32)
