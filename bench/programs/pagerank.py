"""PageRank: the program the session runs, its plain reference, its control.

Semantics (the paper's Example 1, as `repro.core.algorithms.pagerank`
states them): x_0 = 1/n everywhere, and each iteration
x'_i = (1 - d) * sum_{j in N(i)} x_j / max(deg_j, 1) + d / n.
The program computes in float32.
"""
from __future__ import annotations

import numpy as np


def build(params: dict):
    """The program's vertex program, as the session compiles it."""
    from repro.core import algorithms

    return algorithms.pagerank(damping=params["damping"])


def reference(indptr: np.ndarray, indices: np.ndarray, iters: int,
              params: dict) -> np.ndarray:
    """Plain single-machine PageRank in float64; no plan, Shuffle or kernel."""
    n = indptr.size - 1
    d = params["damping"]
    inv_deg = 1.0 / np.maximum(np.diff(indptr), 1)
    starts = indptr[:-1]
    rows = np.flatnonzero(indptr[1:] > starts)
    x = np.full(n, 1.0 / n)
    for _ in range(iters):
        acc = np.zeros(n)
        acc[rows] = np.add.reduceat((x * inv_deg)[indices], starts[rows])
        x = (1.0 - d) * acc + d / n
    return x


def control(indptr: np.ndarray, indices: np.ndarray, iters: int,
            params: dict) -> np.ndarray:
    """The reference in bfloat16, one precision below the program's
    float32, computed on the default device: what `correct` must refuse."""
    import jax
    import jax.numpy as jnp

    n = indptr.size - 1
    d = params["damping"]
    bf = jnp.bfloat16
    rows = jnp.asarray(np.repeat(np.arange(n, dtype=np.int32),
                                 np.diff(indptr)))
    cols = jnp.asarray(indices)
    deg = jnp.asarray(np.maximum(np.diff(indptr), 1), bf)

    @jax.jit
    def step(x):
        acc = jax.ops.segment_sum((x / deg)[cols], rows, n,
                                  indices_are_sorted=True)
        return ((1.0 - d) * acc + d / n).astype(bf)

    x = jnp.full(n, 1.0 / n, bf)
    for _ in range(iters):
        x = step(x)
    return np.asarray(x.astype(jnp.float32), np.float64)


def compare(state: np.ndarray, ref: np.ndarray) -> dict[str, float]:
    """The numbers `correct` is decided on: the largest relative gap of any
    vertex's rank from the reference's (every reference rank is >= d/n)."""
    state = np.asarray(state, np.float64)
    if state.shape != ref.shape or not np.isfinite(state).all():
        return {"rank_max_rel_err": float("inf")}
    return {"rank_max_rel_err": float(np.max(np.abs(state - ref) / ref))}
