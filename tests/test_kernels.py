"""Per-kernel shape/dtype sweeps: pallas_call (interpret=True) vs ref.py."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels.spmv import ops as spmv_ops
from repro.kernels.spmv import ref as spmv_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref
from repro.kernels.xor_code import ops as xor_ops
from repro.kernels.xor_code import ref as xor_ref

RNG = np.random.default_rng(123)


# ---------------- spmv ----------------

@pytest.mark.parametrize("m,n", [(128, 128), (256, 384), (300, 300), (100, 250),
                                 (1, 128), (128, 1)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_spmv_matches_ref(m, n, dtype):
    adj = (RNG.random((m, n)) < 0.2).astype(dtype)
    x = RNG.standard_normal(n).astype(dtype)
    got = spmv_ops.spmv(jnp.array(adj), jnp.array(x))
    want = spmv_ref.spmv(jnp.array(adj), jnp.array(x))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("bm,bk", [(64, 64), (128, 256), (256, 128)])
def test_spmv_block_shape_sweep(bm, bk):
    adj = (RNG.random((512, 512)) < 0.1).astype(np.float32)
    x = RNG.standard_normal(512).astype(np.float32)
    got = spmv_ops.spmv(jnp.array(adj), jnp.array(x), bm=bm, bk=bk)
    want = spmv_ref.spmv(jnp.array(adj), jnp.array(x))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_spmv_pagerank_step_matches_engine_oracle():
    from repro.core import algorithms as algo
    from repro.core import graph_models as gm
    g = gm.erdos_renyi(200, 0.1, seed=5)
    prog = algo.pagerank()
    ref_state = algo.reference_run(prog, g, 1)
    got = spmv_ops.pagerank_step(jnp.array(g.adj, jnp.float32),
                                 jnp.array(prog.init(g)))
    np.testing.assert_allclose(got, ref_state, rtol=1e-5, atol=1e-7)


# ---------------- xor_code ----------------

@pytest.mark.parametrize("r,c,w", [(1, 10, 1), (2, 256, 1), (3, 511, 2),
                                   (4, 1000, 4), (8, 37, 8)])
def test_xor_encode_matches_ref(r, c, w):
    rows = RNG.integers(0, 2**32, size=(r, c, w), dtype=np.uint32)
    valid = RNG.random((r, c)) < 0.6
    got = xor_ops.xor_encode(jnp.array(rows), jnp.array(valid))
    want = xor_ref.xor_encode(jnp.array(rows), jnp.array(valid))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("r", [2, 3, 5])
def test_xor_roundtrip_recovers_missing_row(r):
    """encode(all rows) XOR encode(known rows) == the unknown row."""
    c, w = 300, 2
    rows = RNG.integers(0, 2**32, size=(r, c, w), dtype=np.uint32)
    valid = np.ones((r, c), dtype=bool)
    valid[:, 250:] = RNG.random((r, 50)) < 0.5
    coded = xor_ops.xor_encode(jnp.array(rows), jnp.array(valid))
    dec = xor_ops.xor_decode(coded, jnp.array(rows[1:]), jnp.array(valid[1:]))
    want = np.where(valid[0][:, None], rows[0], 0)
    np.testing.assert_array_equal(np.asarray(dec), want)


@pytest.mark.parametrize("w,r,b", [(1000, 2, None), (300, 3, 4), (0, 2, None),
                                   (0, 2, 3)])
def test_xor_encode_slots_matches_numpy(w, r, b):
    """The per-shard encode (gather, align, XOR-fold through the kernel's
    mask-free route) against a plain NumPy fold; b is the payload width."""
    L = 500
    loc = RNG.integers(0, 2**32, size=(L + 1,) if b is None else (L + 1, b),
                       dtype=np.uint32)
    loc[L] = 0                                       # sentinel
    idx = RNG.integers(0, L + 1, size=(w, r)).astype(np.int32)
    shift = RNG.integers(0, 32, size=(w, r)).astype(np.uint32)
    mask = RNG.integers(0, 2**32, size=(w, r), dtype=np.uint32)
    bx = (lambda a: a) if b is None else (lambda a: a[..., None])
    slotw = (loc[idx] << bx(shift)) & bx(mask)       # [W, r(, B)]
    want = np.bitwise_xor.reduce(slotw, axis=1)
    got = xor_ops.xor_encode_slots(*map(jnp.asarray, (loc, idx, shift, mask)))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_xor_float_bitcast_roundtrip():
    x = RNG.standard_normal(64).astype(np.float32)
    w = xor_ops.floats_as_words(jnp.array(x))
    back = xor_ops.words_as_floats(w)
    np.testing.assert_array_equal(np.asarray(back).view(np.uint32),
                                  x.view(np.uint32))


# ---------------- ssd_scan ----------------

def _ssd_inputs(G, L, P, N, dtype=np.float32):
    return (RNG.standard_normal((G, L, P)).astype(dtype),
            RNG.uniform(0.01, 0.2, (G, L)).astype(dtype),
            (-RNG.uniform(0.5, 2.0, G)).astype(dtype),
            RNG.standard_normal((G, L, N)).astype(dtype),
            RNG.standard_normal((G, L, N)).astype(dtype),
            RNG.standard_normal(G).astype(dtype))


@pytest.mark.parametrize("G,L,P,N,chunk", [
    (1, 64, 8, 4, 16), (2, 128, 16, 8, 32), (3, 128, 32, 16, 64),
    (2, 256, 8, 8, 128), (1, 32, 64, 32, 32),
])
def test_ssd_matches_sequential_ref(G, L, P, N, chunk):
    args = _ssd_inputs(G, L, P, N)
    y, h = ssd_ops.ssd(*map(jnp.array, args), chunk=chunk)
    y_ref, h_ref = ssd_ref.ssd_scan_batched(*map(jnp.array, args))
    np.testing.assert_allclose(y, y_ref, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(h, h_ref, rtol=5e-4, atol=5e-4)


def test_ssd_chunk_invariance():
    args = _ssd_inputs(2, 128, 16, 8)
    y32, h32 = ssd_ops.ssd(*map(jnp.array, args), chunk=32)
    y64, h64 = ssd_ops.ssd(*map(jnp.array, args), chunk=64)
    np.testing.assert_allclose(y32, y64, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(h32, h64, rtol=5e-4, atol=5e-4)


def test_ssd_h0_continuation():
    """Scanning [first half] then [second half with h0] == one full scan."""
    args = _ssd_inputs(2, 128, 8, 4)
    x, dt, A, B, C, D = map(jnp.array, args)
    y_full, h_full = ssd_ops.ssd(x, dt, A, B, C, D, chunk=32)
    y1, h1 = ssd_ops.ssd(x[:, :64], dt[:, :64], A, B[:, :64], C[:, :64], D,
                         chunk=32)
    y2, h2 = ssd_ops.ssd(x[:, 64:], dt[:, 64:], A, B[:, 64:], C[:, 64:], D,
                         h0=h1, chunk=32)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(h2, h_full, rtol=5e-4, atol=5e-4)


def test_ssd_decode_step_extends_scan():
    args = _ssd_inputs(2, 64, 8, 4)
    x, dt, A, B, C, D = map(jnp.array, args)
    _, h = ssd_ops.ssd(x, dt, A, B, C, D, chunk=32)
    xe, dte = x[:, -1], dt[:, -1]
    y_step, h_step = ssd_ops.ssd_decode_step(xe, dte, A, B[:, -1], C[:, -1], D, h)
    assert y_step.shape == (2, 8) and h_step.shape == h.shape
    assert np.isfinite(np.asarray(y_step)).all()
