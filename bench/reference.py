"""The plain reference of a cell's answers, and the comparison that decides
``correct``.

The reference is a brute force over the same host corpus that the program
was given, independent of ``repro``: a float32 pass on the device picks each
query's ``CANDIDATES`` nearest rows by ``||x||^2 - 2 x.q + ||q||^2``, and a
float64 pass on the host ranks those candidates exactly.  The device pass
runs after the program's state is freed, over row chunks, so the corpus is
never held whole on the device.

The control is the same brute force one precision lower than the
configuration states (``high``, three bf16 passes, for float32 at
``highest``): its own float32 top-k stands in for the program's answers.
A CPU computes ``high`` as full float32, so there only the ``bfloat16``
rung below it (operands cast explicitly) can play the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CANDIDATES = 32          # device candidates per query before the f64 rank
ROW_CHUNK = 1 << 17
QUERY_CHUNK = 512
#: two distances closer than this many float32 ulps of ||x||^2 + ||q||^2
#: are a tie: float32 cannot order them
TIE_ULPS = 16
EPS32 = float(np.finfo(np.float32).eps)
PRECISIONS = ("highest", "high", "bfloat16")


def matmul(q, x, precision: str):
    """``q @ x.T`` with float32 accumulation, products at ``precision``:
    ``highest`` (float32; six bf16 passes on a TPU), ``high`` (three bf16
    passes on a TPU; float32 on a CPU) or ``bfloat16`` (both operands
    rounded to bf16 once, on any device)."""
    if precision in ("highest", "high"):
        return jnp.matmul(q, x.T, precision=jax.lax.Precision[precision.upper()])
    if precision == "bfloat16":
        return jnp.matmul(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T,
                          preferred_element_type=jnp.float32)
    raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")


@functools.partial(jax.jit, static_argnames=("cand", "precision"))
def _chunk_topk(q, x, base, n, best_d, best_i, cand: int, precision: str):
    d = ((x * x).sum(1)[None, :] - 2.0 * matmul(q, x, precision)
         + (q * q).sum(1)[:, None])
    ids = base + jnp.arange(x.shape[0], dtype=jnp.int32)
    d = jnp.where(ids[None, :] < n, d, jnp.inf)
    d = jnp.concatenate([best_d, d], 1)
    i = jnp.concatenate([best_i, jnp.broadcast_to(ids, (q.shape[0],
                                                        x.shape[0]))], 1)
    neg, pos = jax.lax.top_k(-d, cand)
    return -neg, jnp.take_along_axis(i, pos, 1)


def device_topk(X: np.ndarray, Q: np.ndarray, cand: int, precision: str,
                *, row_chunk: int = ROW_CHUNK, query_chunk: int = QUERY_CHUNK):
    """(dists (S, cand) float32 ascending, ids (S, cand)) by brute force on
    the default device.  The last row chunk is padded with zero rows whose
    ids lie beyond N and whose distance is +inf, so one program serves
    every chunk."""
    n = X.shape[0]
    row_chunk = min(row_chunk, n)
    cand = min(cand, n)
    out_d, out_i = [], []
    for qlo in range(0, Q.shape[0], query_chunk):
        q = jnp.asarray(Q[qlo:qlo + query_chunk])
        best_d = jnp.full((q.shape[0], cand), jnp.inf, jnp.float32)
        best_i = jnp.full((q.shape[0], cand), -1, jnp.int32)
        for lo in range(0, n, row_chunk):
            xc = X[lo:lo + row_chunk]
            if xc.shape[0] < row_chunk:
                xc = np.concatenate([xc, np.zeros(
                    (row_chunk - xc.shape[0], X.shape[1]), np.float32)])
            best_d, best_i = _chunk_topk(q, jnp.asarray(xc), lo, n, best_d,
                                         best_i, cand, precision)
        out_d.append(np.asarray(best_d))
        out_i.append(np.asarray(best_i))
    return np.concatenate(out_d), np.concatenate(out_i).astype(np.int64)


def exact_d64(X: np.ndarray, Q: np.ndarray, ids: np.ndarray):
    """Float64 squared distances of ``ids`` (S, m) to their queries, and the
    float32 tie scale ``TIE_ULPS * eps32 * (||x||^2 + ||q||^2)``."""
    rows = X[np.clip(ids, 0, X.shape[0] - 1)].astype(np.float64)
    q = Q.astype(np.float64)[:, None, :]
    d = ((rows - q) ** 2).sum(-1)
    scale = EPS32 * ((rows ** 2).sum(-1) + (q ** 2).sum(-1))
    return d, scale


def reference_topk(X: np.ndarray, Q: np.ndarray, k: int):
    """Exact top-``k`` (ids, float64 squared distances) of each query."""
    _, cand = device_topk(X, Q, CANDIDATES, "highest")
    d, scale = exact_d64(X, Q, cand)
    order = np.argsort(d, 1, kind="stable")
    ids = np.take_along_axis(cand, order, 1)
    d = np.take_along_axis(d, order, 1)
    sc = np.take_along_axis(scale, order, 1)
    if cand.shape[1] > k and (d[:, k - 1] + TIE_ULPS * sc[:, k - 1]
                              >= d[:, -1]).any():
        # the k-th neighbour is not clear of the last candidate: the float32
        # candidate pass may have missed a true neighbour
        raise RuntimeError("reference: too few device candidates to rank "
                           f"the top-{k} exactly")
    return ids[:, :k], d[:, :k]


def control_topk(X: np.ndarray, Q: np.ndarray, k: int, precision: str):
    """The control: the reference's device pass at ``precision``, its own
    top-k (ids, float32 distances) taken as the answer."""
    d, ids = device_topk(X, Q, k, precision)
    return ids, d


def compare(ids, dists, X, Q, ref_ids, ref_d) -> dict:
    """Numbers of one set of answers against the reference.

    ``wrong_ranks``: (query, rank) entries whose returned id is invalid,
    repeated within its query, or lies farther from the reference's
    neighbour of that rank than a float32 tie (``TIE_ULPS``).
    ``dist_err_ulps``: the widest gap between a returned distance and the
    float64 distance of its id, in float32 ulps of ``||x||^2 + ||q||^2``,
    over the entries that are valid and finite (the others are wrong)."""
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    if ids.shape != ref_ids.shape:
        raise ValueError(f"answers {ids.shape} vs reference {ref_ids.shape}")
    valid = (ids >= 0) & (ids < X.shape[0])
    srt = np.sort(np.where(valid, ids, -1 - np.arange(ids.shape[1])), 1)
    dup_rows = (srt[:, 1:] == srt[:, :-1]).any(1)
    d64, scale = exact_d64(X, Q, ids)
    off = np.abs(d64 - ref_d) > TIE_ULPS * scale
    ok = valid & np.isfinite(dists)
    wrong = ~ok | off | dup_rows[:, None]
    err = np.where(ok, np.abs(dists - d64) / scale, 0.0)
    return {"wrong_ranks": int(wrong.sum()),
            "dist_err_ulps": float(err.max()) if err.size else 0.0}
