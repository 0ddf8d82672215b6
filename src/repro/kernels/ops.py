"""jit'd public wrappers for the Pallas kernels: shape padding.

``interpret=None`` (the default) compiles the kernels with Mosaic when JAX's
default backend is a TPU and runs them in interpret mode otherwise, so the
same call sites serve CPU tests and the chip.  Pass ``interpret=True`` to
force interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dco_scan import dco_scan, dco_scan_grouped
from repro.kernels.pq_lookup import pq_lookup


def _on_tpu() -> bool:
    """True when JAX's default backend is a TPU (errors propagate)."""
    return jax.default_backend() == "tpu"


def _resolve_interpret(interpret) -> bool:
    return (not _on_tpu()) if interpret is None else interpret


def _pad_to(a, axis, mult, value=0.0):
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "block_d",
                                             "interpret"))
def dco_scan_op(x, q, tau, scales, nrows=None, *, block_n=256, block_q=128,
                block_d=128, interpret=None):
    """Padded staged-scan: arbitrary (N, Q, d1); returns (partial, keep,
    counts, dims) with partial/keep trimmed back to the logical shape.
    ``nrows`` (optional traced scalar) marks how many leading rows of ``x``
    are real — rows at or beyond it never keep and never count (the
    streaming engine passes the valid-row count of its last corpus block).
    Pad rows get partial=large, keep=0, and contribute nothing to ``counts``
    or ``dims``; dim blocks introduced by d1 padding have logical width 0 so
    they never inflate ``dims``."""
    interpret = _resolve_interpret(interpret)
    n, d1 = x.shape
    nq = q.shape[0]
    xp = _pad_to(_pad_to(x, 0, block_n), 1, block_d)
    qp = _pad_to(_pad_to(q, 0, block_q), 1, block_d)
    taup = _pad_to(tau, 0, block_q, value=-1.0)     # pad queries prune all
    nd = xp.shape[1] // block_d
    sc = scales
    if sc.shape[0] < nd:                            # extend schedule for padding
        sc = jnp.concatenate([sc, jnp.repeat(sc[-1:], nd - sc.shape[0])])
    w = np.clip(d1 - np.arange(nd) * block_d, 0, block_d).astype(np.float32)
    nr = jnp.reshape(jnp.asarray(n if nrows is None else nrows, jnp.int32), (1,))
    partial, keep, counts, dims = dco_scan(
        xp, qp, taup, sc[:nd], jnp.asarray(w), nr, block_n=block_n,
        block_q=block_q, block_d=block_d, interpret=interpret)
    return partial[:n, :nq], keep[:n, :nq], counts[:, 0, :nq], dims[:, 0, :nq]


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "interpret"))
def dco_scan_grouped_op(x, q, tau, scales, widths, nrows=None, *, block_n=256,
                        block_q=128, interpret=None):
    """Padded PDX-layout staged scan: x (G, N, dg) dim-group-major corpus,
    q (G, Q, dg) queries split the same way, ``widths`` (G,) f32 the logical
    (unpadded) dim count of each group.  Pads N/Q to tile multiples and dg
    to a lane multiple with zeros (zero dims contribute nothing to the
    squared-distance partials, so values are unchanged).  Returns (partial,
    keep, counts, dims) trimmed like :func:`dco_scan_op`."""
    interpret = _resolve_interpret(interpret)
    _, n, dg = x.shape
    nq = q.shape[1]
    lane = 8 if interpret else 128                  # lane multiple only on TPU
    xp = _pad_to(_pad_to(x, 1, block_n), 2, lane)
    qp = _pad_to(_pad_to(q, 1, block_q), 2, lane)
    taup = _pad_to(tau, 0, block_q, value=-1.0)     # pad queries prune all
    nr = jnp.reshape(jnp.asarray(n if nrows is None else nrows, jnp.int32), (1,))
    partial, keep, counts, dims = dco_scan_grouped(
        xp, qp, taup, scales, widths.astype(jnp.float32), nr,
        block_n=block_n, block_q=block_q, interpret=interpret)
    return partial[:n, :nq], keep[:n, :nq], counts[:, 0, :nq], dims[:, 0, :nq]


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "interpret"))
def pq_lookup_op(codes, lut, *, block_n=128, block_q=8, interpret=None):
    interpret = _resolve_interpret(interpret)
    n = codes.shape[0]
    nq = lut.shape[0]
    cp = _pad_to(codes.astype(jnp.int32), 0, block_n, value=0)
    lp = _pad_to(lut, 0, block_q)
    out = pq_lookup(cp, lp, block_n=block_n, block_q=block_q,
                    interpret=interpret)
    return out[:n, :nq]
