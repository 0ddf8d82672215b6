"""Pallas kernel validation: shape/dtype sweeps + hypothesis vs ref.py
oracles (interpret mode on CPU; same code targets TPU, where
tests/test_tpu_compile.py checks that it compiles)."""
import zlib

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.ops import dco_scan_op, pq_lookup_op


def _seed(*parts) -> int:
    """Stable cross-process seed (builtin hash() is salted by PYTHONHASHSEED,
    which made every pytest process draw different test data)."""
    return zlib.crc32(repr(parts).encode()) % 2 ** 31


@pytest.mark.parametrize("n,q,d1", [
    (256, 128, 128), (300, 17, 130), (64, 8, 96), (1000, 5, 256), (128, 1, 32),
])
@pytest.mark.parametrize("kind", ["lb", "adsampling", "ratio"])
def test_dco_scan_matches_ref(n, q, d1, kind):
    rng = np.random.default_rng(_seed(n, q, d1, kind))
    x = jnp.asarray(rng.standard_normal((n, d1)), jnp.float32)
    qq = jnp.asarray(rng.standard_normal((q, d1)), jnp.float32)
    tau = jnp.asarray(rng.uniform(d1 * 0.5, d1 * 2.5, q), jnp.float32)
    scales = ref.make_dco_scales(kind, d1, 128, D=2 * d1, theta=0.8)
    p1, k1, c1, _ = dco_scan_op(x, qq, tau, scales)
    p2, k2 = ref.dco_scan_ref(x, qq, tau, scales, 128)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                               rtol=1e-4, atol=1e-3)
    assert (np.asarray(k1) == np.asarray(k2)).all()
    c2 = ref.block_keep_counts_ref(k2, 256)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_dco_scan_nrows_masks_padding():
    """Rows at or beyond nrows never keep and never count — the streaming
    engine relies on this for its last (ragged) corpus block."""
    rng = np.random.default_rng(_seed("nrows"))
    n, q, d1, nvalid = 300, 9, 64, 210
    x = jnp.asarray(rng.standard_normal((n, d1)), jnp.float32)
    qq = jnp.asarray(rng.standard_normal((q, d1)), jnp.float32)
    tau = jnp.asarray(rng.uniform(d1, d1 * 3.0, q), jnp.float32)
    scales = ref.make_dco_scales("lb", d1, 64, D=d1)
    _, k_full, _, _ = dco_scan_op(x, qq, tau, scales, block_d=64)
    _, k_cut, c_cut, _ = dco_scan_op(x, qq, tau, scales, nvalid, block_d=64)
    k_full, k_cut = np.asarray(k_full), np.asarray(k_cut)
    np.testing.assert_array_equal(k_cut[:nvalid], k_full[:nvalid])
    assert (k_cut[nvalid:] == 0).all()
    np.testing.assert_array_equal(np.asarray(c_cut).sum(0), k_cut.sum(0))


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("n,q,m,k", [(300, 9, 16, 256), (128, 8, 8, 64),
                                     (65, 3, 4, 16)])
def test_pq_lookup_matches_ref(n, q, m, k, dtype):
    rng = np.random.default_rng(_seed(n, q, m, k))
    codes = jnp.asarray(rng.integers(0, k, (n, m)), jnp.int32)
    lut = jnp.asarray(rng.standard_normal((q, m, k)), dtype)
    a1 = pq_lookup_op(codes, lut)
    a2 = ref.pq_lookup_ref(codes, lut)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(16, 200), q=st.integers(1, 20),
       d1=st.integers(8, 160), seed=st.integers(0, 2**16))
def test_dco_scan_hypothesis(n, q, d1, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d1)), jnp.float32)
    qq = jnp.asarray(rng.standard_normal((q, d1)), jnp.float32)
    tau = jnp.asarray(rng.uniform(0, d1 * 3.0, q), jnp.float32)
    scales = ref.make_dco_scales("lb", d1, 64, D=d1)
    p1, k1, c1, _ = dco_scan_op(x, qq, tau, scales, block_n=64, block_q=32,
                             block_d=64)
    p2, k2 = ref.dco_scan_ref(x, qq, tau, scales, 64)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                               rtol=1e-4, atol=1e-3)
    assert (np.asarray(k1) == np.asarray(k2)).all()
    np.testing.assert_array_equal(np.asarray(c1),
                                  np.asarray(ref.block_keep_counts_ref(k2, 64)))


def test_dco_scan_keep_semantics():
    """keep=1 rows are exactly those whose final scaled partial <= tau."""
    rng = np.random.default_rng(0)
    n, q, d1 = 128, 4, 64
    x = jnp.asarray(rng.standard_normal((n, d1)), jnp.float32)
    qq = jnp.asarray(rng.standard_normal((q, d1)), jnp.float32)
    tau = jnp.asarray(rng.uniform(20, 150, q), jnp.float32)
    scales = ref.make_dco_scales("lb", d1, 64, D=d1)
    p, k, c, _ = dco_scan_op(x, qq, tau, scales, block_d=64)
    p, k = np.asarray(p), np.asarray(k)
    full = ((np.asarray(x)[:, None] - np.asarray(qq)[None]) ** 2).sum(-1)
    # single dim-block => partial == full, keep == (full <= tau)
    np.testing.assert_allclose(p, full, rtol=1e-4, atol=1e-3)
    assert (k.astype(bool) == (full <= np.asarray(tau)[None, :])).all()
    np.testing.assert_array_equal(np.asarray(c).sum(0), k.sum(0))


@pytest.mark.parametrize("kind", ["lb", "adsampling"])
@pytest.mark.parametrize("n,q,d1,nvalid", [(256, 9, 128, None),
                                           (300, 5, 96, 210)])
def test_dco_scan_dims_matches_ref(n, q, d1, nvalid, kind):
    """The kernel's dims output (rows x dims actually read per block, the
    dims_read_mean telemetry) must match the gating-faithful oracle."""
    rng = np.random.default_rng(_seed("dims", n, q, d1, kind))
    x = jnp.asarray(rng.standard_normal((n, d1)), jnp.float32)
    qq = jnp.asarray(rng.standard_normal((q, d1)), jnp.float32)
    tau = jnp.asarray(rng.uniform(d1 * 0.3, d1 * 1.5, q), jnp.float32)
    scales = ref.make_dco_scales(kind, d1, 64, D=2 * d1)
    _, _, _, dims = dco_scan_op(x, qq, tau, scales, nvalid, block_n=64,
                                block_d=64)
    dims2 = ref.dco_scan_dims_ref(x, qq, tau, scales, 64, 64, nvalid)
    np.testing.assert_allclose(np.asarray(dims), np.asarray(dims2))


@pytest.mark.parametrize("n,q,G,dg,nvalid", [(256, 9, 4, 16, None),
                                             (300, 5, 3, 32, 220),
                                             (128, 8, 1, 64, None)])
def test_dco_scan_grouped_matches_flat_blocks(n, q, G, dg, nvalid):
    """The grouped (PDX, 3D x) kernel entry must agree exactly with the flat
    kernel run at block_d == dg: same dim-block boundaries, same gating,
    same accumulation order — partial, keep, counts and dims all match."""
    from repro.kernels.ops import dco_scan_grouped_op

    d1 = G * dg
    rng = np.random.default_rng(_seed("grouped", n, q, G, dg))
    x = jnp.asarray(rng.standard_normal((n, d1)), jnp.float32)
    qq = jnp.asarray(rng.standard_normal((q, d1)), jnp.float32)
    tau = jnp.asarray(rng.uniform(d1 * 0.3, d1 * 1.5, q), jnp.float32)
    scales = ref.make_dco_scales("lb", d1, dg, D=d1)
    p0, k0, c0, a0 = dco_scan_op(x, qq, tau, scales, nvalid, block_n=64,
                                 block_d=dg)
    xg = jnp.moveaxis(x.reshape(n, G, dg), 1, 0)
    qg = jnp.moveaxis(qq.reshape(q, G, dg), 1, 0)
    widths = jnp.full((G,), dg, jnp.float32)
    p1, k1, c1, a1 = dco_scan_grouped_op(xg, qg, tau, scales, widths, nvalid,
                                         block_n=64)
    np.testing.assert_allclose(np.asarray(p0), np.asarray(p1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(k0), np.asarray(k1))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    np.testing.assert_allclose(np.asarray(a0), np.asarray(a1))
