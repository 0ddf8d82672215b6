"""Corpus and query pools of a configuration, generated on the device.

A copy of the ``wikipedia``/``laion`` families of ``repro.vecdata.synthetic``
(anisotropic Gaussian mixture with a power-law spectrum under a random
rotation, and for ``laion`` a cross-modal draw with a flatter spectrum, fewer
clusters, a wider spread and a rotation of its own, rescaled to the corpus'
mean norm), written with ``jax.random`` so that a 1M-row corpus is drawn in a
fraction of a second instead of tens on the host.

The family's structure (cluster centres, rotations) is fixed by the
configuration's ``structure_seed``: it is the deployment's data distribution.
The rows of the corpus and the query pools are drawn from ``--seed``.  Rows
come in fixed-size chunks, one compiled program for all of them, and each
chunk goes to the host as soon as it is made, so the generator never holds
more than a chunk on the device.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK_ROWS = 1 << 17


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed: ``jax.random.key`` keeps only
    the low 32 bits, so the high ones are folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@dataclass
class Data:
    """Host corpus and query pools (``pools[kind]``, (P, D) float32)."""

    X: np.ndarray
    pools: dict


@functools.partial(jax.jit, static_argnames=("dim", "n_clusters"))
def _structure(key, dim: int, n_clusters: int, alpha: float):
    """Cluster centres (n_clusters, dim), spectrum scales (dim,) and a Haar
    rotation (dim, dim)."""
    kc, kr = jax.random.split(key)
    scales = jnp.arange(1, dim + 1, dtype=jnp.float32) ** -alpha
    centers = jax.random.normal(kc, (n_clusters, dim), jnp.float32) * scales * 3.0
    rot, _ = jnp.linalg.qr(jax.random.normal(kr, (dim, dim), jnp.float32))
    return centers, scales, rot


@functools.partial(jax.jit, static_argnames=("rows",))
def _draw(key, centers, scales, rot, spread, rows: int):
    """``rows`` mixture rows under the rotation, and the sum of their norms."""
    ka, kz = jax.random.split(key)
    assign = jax.random.randint(ka, (rows,), 0, centers.shape[0])
    z = jax.random.normal(kz, (rows, centers.shape[1]), jnp.float32)
    x = jnp.matmul(centers[assign] + z * scales * spread, rot,
                   precision=HIGHEST)
    return x, jnp.sqrt((x * x).sum(1)).sum()


def _rows(key, struct, spread: float, n: int, chunk: int):
    """Draw ``n`` rows chunk by chunk to the host; returns (X, norm sum)."""
    out = np.empty((n, struct[0].shape[1]), np.float32)
    norm_sum = 0.0
    for c, lo in enumerate(range(0, n, chunk)):
        x, s = _draw(jax.random.fold_in(key, c), *struct, spread, chunk)
        hi = min(n, lo + chunk)
        out[lo:hi] = np.asarray(x)[:hi - lo]
        if hi - lo == chunk:
            norm_sum += float(s)
        else:
            norm_sum += float(np.sqrt((out[lo:hi] ** 2).sum(1)).sum())
        del x
    return out, norm_sum


def generate(data: dict, seed: int, *, chunk: int = CHUNK_ROWS) -> Data:
    """The corpus and query pools that ``data`` (a configuration's
    ``"data"`` block) describes, drawn from ``seed``.

    ``pools`` holds ``"id"`` (held-out rows of the corpus' own mixture) and,
    where ``data["ood"]`` is given, ``"ood"`` (the cross-modal draw scaled to
    the corpus' mean row norm)."""
    n, dim, pool = int(data["n"]), int(data["dim"]), int(data["pool"])
    skey = seed_key(int(data["structure_seed"]))
    struct = _structure(jax.random.fold_in(skey, 0), dim,
                        int(data["n_clusters"]), float(data["spectrum_alpha"]))
    key = seed_key(seed)
    chunk = min(chunk, max(n, pool))
    X, norm_sum = _rows(jax.random.fold_in(key, 0), struct, 1.0, n, chunk)
    pools = {"id": _rows(jax.random.fold_in(key, 1), struct, 1.0, pool,
                         chunk)[0]}
    ood = data.get("ood")
    if ood:
        ostruct = _structure(jax.random.fold_in(skey, 1), dim,
                             int(ood["n_clusters"]),
                             float(ood["spectrum_alpha"]))
        Q, qsum = _rows(jax.random.fold_in(key, 2), ostruct,
                        float(ood["spread"]), pool, chunk)
        Q *= np.float32((norm_sum / n) / max(qsum / pool, 1e-9))
        pools["ood"] = Q
    del struct
    return Data(X, pools)
