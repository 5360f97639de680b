"""Public chunked-SSD op: Pallas intra-chunk kernel + associative cross-chunk
state scan. Matches ref.ssd_scan to fp32 tolerance for any chunk size."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ref import ssd_scan_batched
from .ssd_scan import ssd_chunk_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel", "interpret"))
def ssd(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
        C: jnp.ndarray, D: jnp.ndarray, h0: jnp.ndarray | None = None, *,
        chunk: int = 64, use_kernel: bool = True,
        interpret: bool | None = None
        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched SSD. x [G, L, P]; dt [G, L]; A [G]; B/C [G, L, N]; D [G].

    Returns (y [G, L, P], h_final [G, N, P]). L must be a multiple of chunk
    (the model pads); h0 seeds the scan (decode restarts).
    """
    if not use_kernel:
        return ssd_scan_batched(x, dt, A, B, C, D, h0)
    g, L, p = x.shape
    n = B.shape[-1]
    assert L % chunk == 0, (L, chunk)
    ch = L // chunk
    xr = x.reshape(g, ch, chunk, p).astype(jnp.float32)
    dtr = dt.reshape(g, ch, chunk).astype(jnp.float32)
    dta = dtr * A[:, None, None].astype(jnp.float32)
    br = B.reshape(g, ch, chunk, n).astype(jnp.float32)
    cr = C.reshape(g, ch, chunk, n).astype(jnp.float32)

    y_intra, S, G, Cexp = ssd_chunk_pallas(xr, dtr, dta, br, cr,
                                           interpret=interpret)

    # Cross-chunk state: H_c = G_c H_{c-1} + S_c, associative in (G, S).
    def combine(a, b):
        ga, sa = a
        gb, sb = b
        return ga * gb, gb[..., None, None] * sa + sb

    Gs, Ss = jax.lax.associative_scan(combine, (G, S), axis=1)
    h0 = jnp.zeros((g, n, p), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    # H_prev[c] = state entering chunk c.
    h_in = jnp.concatenate([h0[:, None], Gs[:, :-1, None, None] * h0[:, None]
                            + Ss[:, :-1]], axis=1)
    y_inter = jnp.einsum("gcqn,gcnp->gcqp", Cexp, h_in)
    y = (y_intra + y_inter).reshape(g, L, p) + D[:, None, None] * x
    h_final = Gs[:, -1, None, None] * h0 + Ss[:, -1]
    return y, h_final


def ssd_decode_step(x, dt, A, B, C, D, h):
    """Single-token decode: x [G, P], dt [G], B/C [G, N], h [G, N, P]."""
    a = jnp.exp(dt * A)[:, None, None]
    h = a * h + dt[:, None, None] * jnp.einsum("gn,gp->gnp", B, x)
    y = jnp.einsum("gn,gnp->gp", C, h) + D[:, None] * x
    return y, h
