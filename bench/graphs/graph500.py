"""Graph500 Kronecker graphs, processed as LDBC Graphalytics' graph500-*
datasets are: the benchmark's own generator, apart from the program's
graph models, so that a change to the program cannot change the data the
yardstick feeds it.

Written from the Graph500 specification's Kronecker generator (initiator
A, B, C, D; edge factor; vertex labels permuted at random), then made
undirected, with self-loops, repeated edges and vertices that have no edge
removed, as Graphalytics publishes graph500-22.
"""
from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, initiator, seed: int
                    ) -> tuple[np.ndarray, np.ndarray]:
    """The specification's edge list: edge_factor * 2**scale pairs
    (u, v), each bit of each label drawn from the 2x2 initiator, then the
    2**scale labels permuted at random."""
    a, b, c, _ = initiator
    n, m = 1 << scale, edge_factor << scale
    rng = np.random.default_rng(seed)
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        u_bit = rng.random(m) > ab
        v_bit = rng.random(m) > np.where(u_bit, c_norm, a_norm)
        u |= u_bit.astype(np.int64) << bit
        v |= v_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[u], perm[v]


def generate(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The configuration's graph as a symmetric CSR ``(indptr, indices)``,
    every row's columns ascending; vertices are the labels that have an
    edge, in label order."""
    u, v = kronecker_edges(cfg["scale"], cfg["edge_factor"],
                           cfg["initiator"], seed)
    keep = u != v
    lo, hi = np.minimum(u, v)[keep], np.maximum(u, v)[keep]
    span = np.int64(1) << cfg["scale"]
    keys = np.unique(lo * span + hi)                # undirected, no repeats
    lo, hi = keys // span, keys % span
    present = np.zeros(int(span), bool)
    present[lo] = present[hi] = True
    label = np.cumsum(present) - 1                  # order kept: keys stay sorted
    n = int(present.sum())
    lo, hi = label[lo], label[hi]
    both = np.sort(np.concatenate([lo * n + hi, hi * n + lo]))
    rows, cols = both // n, both % n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return indptr, cols.astype(np.int32)
