"""Compile-only checks of the Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what Mosaic would refuse on the
chip (unaligned block shapes, vector reads at dynamic indices, oversized
VMEM).  Each kernel test compiles one kernel at the tiles the streaming
engine selects on a TPU (``core.stream_engine._scan_blocks``) and checks
that the kernel is in the program as a ``tpu_custom_call``; one more
compiles the whole screened scan around it.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dco_scan import dco_scan, dco_scan_grouped
from repro.kernels.pq_lookup import pq_lookup

#: tiles stream_engine._scan_blocks selects when the kernel runs on a TPU
KB = dict(block_n=256, block_q=128, block_d=128)
ROW_BLOCK = 4096            # SchedulePolicy.row_block default
D1 = 128                    # SchedulePolicy.d1 default
QUERY_CHUNK = 16            # SchedulePolicy.query_chunk default
KB_PQ = dict(block_n=128, block_q=QUERY_CHUNK)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scan_args(one_chip, x_shape, q_shape, n_dblocks):
    f32 = jnp.float32
    return (_spec(x_shape, f32, one_chip), _spec(q_shape, f32, one_chip),
            _spec((KB["block_q"],), f32, one_chip),
            _spec((n_dblocks,), f32, one_chip),
            _spec((n_dblocks,), f32, one_chip),
            _spec((1,), jnp.int32, one_chip))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_dco_scan_compiles_for_v5e(one_chip, no_compile_cache):
    args = _scan_args(one_chip, (ROW_BLOCK, D1), (KB["block_q"], D1),
                      D1 // KB["block_d"])
    compiled = jax.jit(
        lambda *a: dco_scan(*a, **KB)).lower(*args).compile()
    _assert_kernel(compiled)


def test_dco_scan_grouped_compiles_for_v5e(one_chip, no_compile_cache):
    groups = 4                  # SchedulePolicy(dim_groups=4): 32 dims each
    lane = 128                  # ops.dco_scan_grouped_op pads them to lanes
    args = _scan_args(one_chip, (groups, ROW_BLOCK, lane),
                      (groups, KB["block_q"], lane), groups)
    compiled = jax.jit(lambda *a: dco_scan_grouped(
        *a, block_n=KB["block_n"], block_q=KB["block_q"])).lower(
            *args).compile()
    _assert_kernel(compiled)


def test_pq_lookup_compiles_for_v5e(one_chip, no_compile_cache):
    n_sub, n_codes = 16, 256    # DDCopq defaults
    codes = _spec((ROW_BLOCK, n_sub), jnp.int32, one_chip)
    lut = _spec((QUERY_CHUNK, n_sub, n_codes), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda c, t: pq_lookup(c, t, **KB_PQ)).lower(codes, lut).compile()
    _assert_kernel(compiled)


def test_screened_scan_compiles_for_v5e(one_chip, no_compile_cache,
                                        monkeypatch):
    """The screened scan as the chip runs it: the dco_scan kernel, then a
    per-block cond between the chunk-shared and the per-query completion,
    over two row blocks of a small corpus.  The engine asks the backend
    whether it is on a TPU; here the test answers for it."""
    from repro.core import stream_engine as se
    from repro.core.jax_engine import DcoEngineConfig
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    nb, dt, nq = 2, 128, 2 * QUERY_CHUNK
    f32 = jnp.float32
    xs = {"xl": _spec((nb, ROW_BLOCK, D1), f32, one_chip),
          "xt": _spec((nb, ROW_BLOCK, dt), f32, one_chip),
          "lsq": _spec((nb, ROW_BLOCK), f32, one_chip),
          "tsq": _spec((nb, ROW_BLOCK), f32, one_chip),
          "ids": _spec((nb, ROW_BLOCK), jnp.int32, one_chip)}
    state = {"tail_sq": _spec((nb * ROW_BLOCK,), f32, one_chip)}
    cfg = DcoEngineConfig(kind="lb", d1=D1, k=10, query_chunk=QUERY_CHUNK,
                          row_block=ROW_BLOCK, block_capacity=128,
                          use_kernel=True)
    compiled = se._stream_topk_padded.lower(
        state, xs, _spec((nq, D1), f32, one_chip),
        _spec((nq, dt), f32, one_chip), {}, None, cfg).compile()
    _assert_kernel(compiled)
    assert "conditional(" in compiled.as_text()
