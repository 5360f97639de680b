"""The Graph500 generator: the specification's Kronecker edges, processed
as Graphalytics processes graph500-*, the same graph for the same seed."""
import numpy as np
import pytest

from bench import harness
from conftest import ROOT

CFG = {"scale": 12, "edge_factor": 16, "initiator": [0.57, 0.19, 0.19, 0.05]}


@pytest.fixture(scope="module")
def graph500():
    return harness._load_module(ROOT / "bench/graphs/graph500.py")


@pytest.mark.parametrize("seed", [0, 2**31 + 17, 2**40 + 3])
def test_undirected_simple_no_isolated(graph500, seed):
    indptr, indices = graph500.generate(CFG, seed)
    n = indptr.size - 1
    rows = np.repeat(np.arange(n), np.diff(indptr))
    assert np.diff(indptr).min() >= 1                       # no isolated
    assert not (rows == indices).any()                      # no self-loops
    keys = rows * n + indices
    assert (np.diff(keys) > 0).all()                        # sorted, no repeats
    assert np.array_equal(np.sort(indices.astype(np.int64) * n + rows), keys)


def test_same_seed_same_graph(graph500):
    a = graph500.generate(CFG, 2**31 + 5)
    b = graph500.generate(CFG, 2**31 + 5)
    c = graph500.generate(CFG, 2**31 + 6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])


def test_kronecker_skew_and_sizes(graph500):
    """Most of the 2**scale labels carry an edge, most of the
    edge_factor * 2**scale drawn edges survive, and degrees are skewed."""
    indptr, _ = graph500.generate(CFG, 7)
    n, deg = indptr.size - 1, np.diff(indptr)
    assert 0.6 < n / 2**CFG["scale"] < 0.95
    assert 0.6 < indptr[-1] / 2 / (CFG["edge_factor"] << CFG["scale"]) < 0.95
    assert deg.max() > 20 * deg.mean()


def test_initiator_bits(graph500):
    """The heaviest label is the one drawn as all zero bits: each bit of u
    is 0 with probability A + B, each of v with A + C, so its share of the
    endpoints is ((A + B)**scale + (A + C)**scale) / 2."""
    a, b, c, _ = CFG["initiator"]
    u, v = graph500.kronecker_edges(10, 16, CFG["initiator"], 3)
    assert u.max() < 2**10 and v.max() < 2**10
    share = np.bincount(np.concatenate([u, v])).max() / (2 * u.size)
    assert share == pytest.approx(((a + b) ** 10 + (a + c) ** 10) / 2,
                                  rel=0.1)


def test_seeds_relabel_one_draw_to_the_same_shapes():
    """The cell's graph for two seeds: one draw relabelled, with the same
    plan sizes and padded exchange shapes, so one compile serves both."""
    from repro.core.allocation import er_allocation
    from repro.core.fused_shuffle import partition_plan
    from repro.core.graph_models import Graph
    from repro.core.shuffle_plan import compile_plan_csr

    c = harness.cell(harness.load_spec(), "graph500-18-k4r2.pagerank")
    c["config"]["scale"] = 11
    shapes, graphs = [], []
    for seed in (3, 2**31 + 3):
        indptr, indices = harness.make_graph(c, seed)
        n = indptr.size - 1
        assert n % 12 == 0
        g = Graph.from_csr(indptr, indices)
        alloc = er_allocation(n, 4, 2)
        plan = compile_plan_csr(g.csr, alloc)
        part = partition_plan(plan, g.csr, alloc)
        shapes.append((n, int(indptr[-1]), plan.col_sender.size,
                       plan.all_k.size, part.W, part.Lmax, part.Dmax))
        deg = np.diff(indptr)
        # degrees move only among vertices the allocation treats alike
        alike = alloc.batch_of * 4 + alloc.reduce_owner
        graphs.append((indices, [np.sort(deg[alike == a])
                                 for a in np.unique(alike)]))
    assert shapes[0] == shapes[1]
    assert not np.array_equal(graphs[0][0], graphs[1][0])
    assert all(np.array_equal(x, y) for x, y in zip(graphs[0][1],
                                                    graphs[1][1]))
