"""Jitted public wrappers around the SpMV kernel (auto-padding + PageRank)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .ref import spmv as spmv_ref
from .spmv import spmv_pallas


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def spmv(adj: jnp.ndarray, x: jnp.ndarray, *, bm: int = 128, bk: int = 128,
         use_kernel: bool = True,
         interpret: bool | None = None) -> jnp.ndarray:
    """y = adj @ x, padding ragged shapes up to the tile grid."""
    if not use_kernel:
        return spmv_ref(adj, x)
    m, n = adj.shape
    a = _pad_to(_pad_to(adj.astype(jnp.float32), bm, 0), bk, 1)
    xp = _pad_to(x.astype(jnp.float32), bk, 0)
    return spmv_pallas(a, xp, bm=bm, bk=bk, interpret=interpret)[:m]


def pagerank_step(adj: jnp.ndarray, rank: jnp.ndarray, damping: float = 0.15,
                  **kw) -> jnp.ndarray:
    deg = jnp.maximum(adj.sum(axis=0), 1.0)
    acc = spmv(adj, rank / deg, **kw)
    return (1.0 - damping) * acc + damping / adj.shape[0]


def spmv_csr_rows(indptr: np.ndarray, indices: np.ndarray, c: np.ndarray,
                  n: int, *, rows: np.ndarray | None = None, bm: int = 128,
                  bk: int = 128, use_kernel: bool = True,
                  interpret: bool | None = None) -> np.ndarray:
    """acc[i] = sum_{j in row i} c[j] from a CSR adjacency, via the Pallas
    kernel in blocked [bm, n] row strips.

    The dense strip is densified from the CSR slice per block, so peak
    memory is O(bm * n) regardless of the row count - the sparse engine's
    `backend="spmv"` Reduce route. Every strip shares one compiled kernel
    (fixed [bm, n_pad] shape; the trailing partial strip is zero-padded).
    Pass the cached per-entry `rows` array (e.g. `Graph.csr.rows`) to avoid
    rebuilding it per call.
    """
    n_pad = n + (-n) % bk
    cj = jnp.asarray(np.pad(np.asarray(c, np.float32), (0, n_pad - n)))
    acc = np.empty(n, dtype=np.float32)
    if rows is None:
        rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    for start in range(0, n, bm):
        stop = min(start + bm, n)
        strip = np.zeros((bm, n_pad), dtype=np.float32)
        a, b = int(indptr[start]), int(indptr[stop])
        strip[rows[a:b] - start, indices[a:b]] = 1.0
        if use_kernel:
            y = spmv_pallas(jnp.asarray(strip), cj, bm=bm, bk=bk,
                            interpret=interpret)
        else:
            y = spmv_ref(jnp.asarray(strip), cj)
        acc[start:stop] = np.asarray(y)[:stop - start]
    return acc
