"""The benchmark's device generators at tiny sizes (on the CPU)."""
import bench_testutil  # noqa: F401  (puts the checkout on sys.path)
import numpy as np
import pytest

from bench import datagen

DATA = {"n": 3000, "dim": 64, "spectrum_alpha": 0.7, "n_clusters": 8,
        "structure_seed": 0, "pool": 200,
        "ood": {"n_clusters": 4, "spectrum_alpha": 0.2, "spread": 1.6}}


@pytest.fixture(scope="module")
def drawn():
    return datagen.generate(DATA, 2**33 + 1, chunk=1024)


def test_shapes_and_kinds(drawn):
    assert drawn.X.shape == (3000, 64) and drawn.X.dtype == np.float32
    assert set(drawn.pools) == {"id", "ood"}
    assert all(q.shape == (200, 64) for q in drawn.pools.values())
    assert np.isfinite(drawn.X).all()


def test_same_seed_same_data_other_seed_other_rows(drawn):
    again = datagen.generate(DATA, 2**33 + 1, chunk=1024)
    np.testing.assert_array_equal(drawn.X, again.X)
    np.testing.assert_array_equal(drawn.pools["ood"], again.pools["ood"])
    other = datagen.generate(DATA, 1, chunk=1024)
    assert not np.allclose(drawn.X, other.X)


def test_seeds_beyond_32_bits_differ():
    k = [np.asarray(datagen.jax.random.key_data(datagen.seed_key(s)))
         for s in (5, 5 + 2**32, 5 + 2**33)]
    assert not np.array_equal(k[0], k[1]) and not np.array_equal(k[1], k[2])
    with pytest.raises(ValueError):
        datagen.seed_key(-1)


def test_ood_queries_are_shifted_away_from_the_corpus(drawn):
    X = drawn.X.astype(np.float64)
    mu = X.mean(0)
    lam, V = np.linalg.eigh(np.cov((X - mu).T))
    top = V[:, ::-1][:, :8]          # the corpus' 8 leading directions

    def lead_share(Q):
        Qc = Q.astype(np.float64) - mu
        return float(((Qc @ top) ** 2).sum() / (Qc ** 2).sum())

    assert lead_share(drawn.pools["id"]) > 0.5
    assert lead_share(drawn.pools["ood"]) < 0.5 * lead_share(drawn.pools["id"])
    # scaled to the corpus' mean row norm
    nx = np.linalg.norm(X, axis=1).mean()
    nq = np.linalg.norm(drawn.pools["ood"], axis=1).mean()
    assert nq == pytest.approx(nx, rel=1e-3)
