"""Chunked Mamba2 SSD Pallas TPU kernel.

State-space duality: within a chunk of Q tokens the recurrence is a small
causal "attention" M = (C B^T) ∘ decay (an MXU matmul per tile); across chunks
only an [N, P] state is carried. The kernel computes, per (head, chunk):

  y_intra[t] = sum_{s<=t} (C_t.B_s) dt_s e^{cum_t-cum_s} x_s
  S          = sum_s e^{cum_Q-cum_s} dt_s B_s x_s^T     (chunk-local end state)
  G          = e^{cum_Q}                                (chunk decay)
  Cexp[t]    = C_t e^{cum_t}                            (inter-chunk readout)

ops.py stitches chunks with an associative scan over (G, S) - the only
sequential dependence, O(L/Q) instead of O(L).
VMEM working set per grid step: Q*(P+2N) inputs + Q^2 scores + N*P state;
Q=128/256 with P,N<=128 keeps it well under 16 MB at fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import interpret_mode


def _ssd_chunk_kernel(x_ref, dt_ref, dta_ref, b_ref, c_ref,
                      y_ref, s_ref, g_ref, cexp_ref):
    x = x_ref[0, 0].astype(jnp.float32)        # [Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)      # [Q]
    dta = dta_ref[0, 0].astype(jnp.float32)    # [Q]  (= dt * A, <= 0)
    b = b_ref[0, 0].astype(jnp.float32)        # [Q, N]
    c = c_ref[0, 0].astype(jnp.float32)        # [Q, N]
    q = x.shape[0]

    cum = jnp.cumsum(dta)                      # [Q], inclusive
    # Intra-chunk causal scores: M[t, s] = (C_t.B_s) dt_s e^{cum_t - cum_s}.
    scores = jnp.dot(c, b.T, preferred_element_type=jnp.float32)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = s_idx <= t_idx
    # Mask inside the exp (upper triangle would overflow / break backward).
    decay = jnp.exp(jnp.where(tri, cum[:, None] - cum[None, :], -1e30))
    m = scores * decay * dt[None, :]
    y_ref[0, 0] = jnp.dot(m, x, preferred_element_type=jnp.float32)

    # Chunk-local end state and decay.
    w = jnp.exp(cum[-1] - cum) * dt            # [Q]
    s_ref[0, 0] = jnp.dot((b * w[:, None]).T, x,
                          preferred_element_type=jnp.float32)
    g_ref[0, 0] = jnp.exp(cum[-1])
    cexp_ref[0, 0] = c * jnp.exp(cum)[:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(x, dt, dta, b, c, *, interpret: bool | None = None):
    """x [G, Ch, Q, P]; dt/dta [G, Ch, Q]; b/c [G, Ch, Q, N].

    -> y_intra [G, Ch, Q, P], S [G, Ch, N, P], Gdecay [G, Ch], Cexp [G, Ch, Q, N]
    """
    g, ch, q, p = x.shape
    n = b.shape[-1]
    grid = (g, ch)
    specs4 = lambda d3, d4: pl.BlockSpec((1, 1, d3, d4), lambda i, j: (i, j, 0, 0))
    spec3 = pl.BlockSpec((1, 1, q), lambda i, j: (i, j, 0))
    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[specs4(q, p), spec3, spec3, specs4(q, n), specs4(q, n)],
        out_specs=(specs4(q, p), specs4(n, p),
                   pl.BlockSpec((1, 1), lambda i, j: (i, j)), specs4(q, n)),
        out_shape=(jax.ShapeDtypeStruct((g, ch, q, p), jnp.float32),
                   jax.ShapeDtypeStruct((g, ch, n, p), jnp.float32),
                   jax.ShapeDtypeStruct((g, ch), jnp.float32),
                   jax.ShapeDtypeStruct((g, ch, q, n), jnp.float32)),
        interpret=interpret_mode(interpret),
    )(x, dt, dta, b, c)
