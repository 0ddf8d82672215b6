"""Pallas TPU kernel: staged DCO scan (stage-1 partial distances + screening).

TPU-native form of the paper's incremental dimension scanning (DESIGN.md §3):
the grid walks (query block, candidate block, dim block) with the dim axis
innermost; the ``partial`` output block — resident in VMEM across the whole
dim loop — carries the running partial distance, and after each dim block the
scaled-estimate-vs-tau test freezes pruned (row, query) pairs.  When an
entire (candidate x query) tile is dead, the next dim-block's matmul is
skipped via ``pl.when`` — the block-level early exit that replaces the
paper's per-vector ``break`` (compute is saved; the HBM->VMEM stream for the
skipped tile is the price of keeping the pipeline static, which is the right
trade on TPU where stage-1 is MXU-bound for d1 >= 128).

Two entry points share one kernel body:

  ``dco_scan``          row-major x (N, d1), dim blocks sliced on the fly —
                        the PR 2 layout;
  ``dco_scan_grouped``  PDX-style vertical x (G, N, dg) (DESIGN.md §8): each
                        dim GROUP is a contiguous (N, dg) plane, so the
                        per-dim-block HBM read is a unit-stride stream even
                        when candidates freeze between groups.

Outputs, per call:
  partial (N, Q) f32      running partial distances (frozen rows keep the
                          value at which they were pruned);
  keep    (N, Q) int8     1 iff the final scaled estimate still clears tau
                          AND the row index is < ``nrows`` (padding rows
                          never keep);
  counts  (NB, 8, Q) i32  per-candidate-block keep counts (NB = N / block_n)
                          — what the streaming engine (core.stream_engine)
                          consumes so no (N, Q) array ever has to leave the
                          block loop;
  dims    (NB, 8, Q) f32  dimensions actually entered per candidate block:
                          each dim block adds ``widths[di]`` for every
                          still-alive valid row — the measured early-exit
                          telemetry behind the facade's ``dims_read_mean``.
  The per-block totals are replicated over an 8-row sublane axis: Mosaic
  tiles the last two block dims in (8, 128) units, so a (1, BQ) block of an
  (NB, Q) array does not lower.  ``kernels.ops`` returns row 0 as (NB, Q).

The per-dim-block scalars (``scales``, ``widths``) and ``nrows`` live in
SMEM: they are read at a grid-dependent index, which a VMEM vector block
cannot serve.  The cross term runs at ``Precision.HIGHEST``: the partial is
a certified lower bound only if it is computed in f32, and a default TPU
f32 dot is one bf16 pass.

Tile sizes: x tile (BN, BD), q tile (BQ, BD), accumulator (BN, BQ) — all
MXU-aligned multiples of (8, 128) for f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: sublane rows the per-block ``counts``/``dims`` totals are replicated over
SUBLANES = 8


def _kernel(scales_ref, widths_ref, nrows_ref, x_ref, q_ref, tau_ref,
            out_ref, keep_ref, cnt_ref, dims_ref, *, nd_blocks: int,
            block_n: int):
    di = pl.program_id(2)
    row0 = pl.program_id(1) * block_n

    @pl.when(di == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        dims_ref[...] = jnp.zeros_like(dims_ref)

    tau = tau_ref[...][None, :]                            # (1, BQ)
    prev_scale = scales_ref[jnp.maximum(di - 1, 0)]
    alive = out_ref[...] * prev_scale <= tau               # frozen rows stay dead
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, alive.shape, 0)
    # dims telemetry: every alive valid row 'reads' this dim block's logical
    # width (0 for shape-padding dim blocks), whether or not the tile-level
    # skip below saves the matmul — per-row exit is what the stat measures
    entering = (alive & (row < nrows_ref[0])).astype(jnp.float32)
    dims_ref[...] += jnp.broadcast_to(
        entering.sum(0, keepdims=True) * widths_ref[di], dims_ref.shape)

    @pl.when(jnp.any(alive))
    def _compute():
        xb = x_ref[...]                                    # (BN, BD) / (1, BN, dg)
        xb = xb.reshape(xb.shape[-2], xb.shape[-1])
        qb = q_ref[...]                                    # (BQ, BD) / (1, BQ, dg)
        qb = qb.reshape(qb.shape[-2], qb.shape[-1])
        contrib = ((xb * xb).sum(1, keepdims=True)
                   - 2.0 * jax.lax.dot_general(
                       xb, qb, (((1,), (1,)), ((), ())),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
                   + (qb * qb).sum(1, keepdims=True).T)
        out_ref[...] = jnp.where(alive, out_ref[...] + jnp.maximum(contrib, 0.0),
                                 out_ref[...])

    @pl.when(di == nd_blocks - 1)
    def _finish():
        est = out_ref[...] * scales_ref[di]
        keep = alive & (est <= tau) & (row < nrows_ref[0])
        keep_ref[...] = keep.astype(jnp.int8)
        cnt_ref[...] = jnp.broadcast_to(
            keep.astype(jnp.int32).sum(0, keepdims=True), cnt_ref.shape)


def _out_shapes(n, nq, nnb):
    return [
        jax.ShapeDtypeStruct((n, nq), jnp.float32),
        jax.ShapeDtypeStruct((n, nq), jnp.int8),
        jax.ShapeDtypeStruct((nnb, SUBLANES, nq), jnp.int32),
        jax.ShapeDtypeStruct((nnb, SUBLANES, nq), jnp.float32),
    ]


def _out_specs(block_n, block_q):
    return [
        pl.BlockSpec((block_n, block_q), lambda qi, ni, di: (ni, qi)),
        pl.BlockSpec((block_n, block_q), lambda qi, ni, di: (ni, qi)),
        pl.BlockSpec((None, SUBLANES, block_q), lambda qi, ni, di: (ni, 0, qi)),
        pl.BlockSpec((None, SUBLANES, block_q), lambda qi, ni, di: (ni, 0, qi)),
    ]


def _scalar_specs():
    """scales, widths, nrows: whole arrays in SMEM (dynamic scalar reads)."""
    return [pl.BlockSpec(memory_space=pltpu.SMEM)] * 3


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "block_d",
                                             "interpret"))
def dco_scan(x, q, tau, scales, widths, nrows, *, block_n: int = 256,
             block_q: int = 128, block_d: int = 128, interpret: bool = False):
    """x (N, d1) rotated leading dims; q (Q, d1) rotated queries;
    tau (Q,) squared thresholds; scales (n_dblocks,) estimate multipliers;
    widths (n_dblocks,) f32 logical dims per dim block (0 for padding
    blocks); nrows (1,) i32 count of valid (non-padding) leading rows of x.
    Returns (partial (N, Q) f32, keep (N, Q) int8, counts (N/block_n, 8, Q)
    i32, dims (N/block_n, 8, Q) f32; the 8 rows of a block are equal).
    N, Q, d1 must be tile multiples — ``kernels.ops.dco_scan_op`` pads
    arbitrary shapes."""
    n, d1 = x.shape
    nq = q.shape[0]
    nd = pl.cdiv(d1, block_d)
    nnb = pl.cdiv(n, block_n)
    grid = (pl.cdiv(nq, block_q), nnb, nd)
    kernel = functools.partial(_kernel, nd_blocks=nd, block_n=block_n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            *_scalar_specs(),
            pl.BlockSpec((block_n, block_d), lambda qi, ni, di: (ni, di)),
            pl.BlockSpec((block_q, block_d), lambda qi, ni, di: (qi, di)),
            pl.BlockSpec((block_q,), lambda qi, ni, di: (qi,)),
        ],
        out_specs=_out_specs(block_n, block_q),
        out_shape=_out_shapes(n, nq, nnb),
        interpret=interpret,
    )(scales, widths, nrows, x, q, tau)


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "interpret"))
def dco_scan_grouped(x, q, tau, scales, widths, nrows, *, block_n: int = 256,
                     block_q: int = 128, interpret: bool = False):
    """PDX-layout staged scan: x (G, N, dg) vertical corpus (dim group
    major, each group a contiguous (N, dg) plane), q (G, Q, dg) the queries
    split the same way.  The grid's innermost axis walks GROUPS, so the
    per-group freeze/skip semantics are exactly ``dco_scan``'s per-dim-block
    semantics, but the HBM stream for each group is unit-stride (DESIGN.md
    §8).  Same outputs as :func:`dco_scan`; N, Q, dg must be tile multiples
    (``kernels.ops.dco_scan_grouped_op`` pads)."""
    ng, n, dg = x.shape
    nq = q.shape[1]
    nnb = pl.cdiv(n, block_n)
    grid = (pl.cdiv(nq, block_q), nnb, ng)
    kernel = functools.partial(_kernel, nd_blocks=ng, block_n=block_n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            *_scalar_specs(),
            pl.BlockSpec((1, block_n, dg), lambda qi, ni, di: (di, ni, 0)),
            pl.BlockSpec((1, block_q, dg), lambda qi, ni, di: (di, qi, 0)),
            pl.BlockSpec((block_q,), lambda qi, ni, di: (qi,)),
        ],
        out_specs=_out_specs(block_n, block_q),
        out_shape=_out_shapes(n, nq, nnb),
        interpret=interpret,
    )(scales, widths, nrows, x, q, tau)
