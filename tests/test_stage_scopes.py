"""Device scopes of the streaming engine's stages (``repro.utils.spans``):
every product, sort, top-k, gather and custom call of the served programs
carries a ``dco.*`` scope in its ``op_name``, so a profiler trace can name
the stage that spent each op's time."""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stream_engine as se
from repro.core.jax_engine import DcoEngineConfig, build_device_state
from repro.core.methods import make_method
from repro.core.policy import PolicyConfig
from repro.utils.spans import SCOPES

#: HLO opcodes that do a stage's work and so must name their stage
STAGE_OPCODES = {"dot", "sort", "topk", "gather", "custom-call"}
INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (?:\([^)]*\)|\S+) ([\w-]+)\(")


def _stage_ops(hlo_text):
    """[(instruction, opcode, op_name)] of the stage opcodes in ``hlo_text``,
    fused computations included."""
    out = []
    for line in hlo_text.splitlines():
        m = INSTR.match(line)
        if m and m.group(2) in STAGE_OPCODES:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), op.group(1) if op else ""))
    return out


@pytest.fixture(scope="module")
def tiny():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((2048, 64)).astype(np.float32)
    m = make_method("PDScanning+").fit(X)
    st = build_device_state(m, 16)
    Q = jnp.asarray(rng.standard_normal((16, 64)).astype(np.float32))
    return st, se.build_stream_blocks(st, 512), Q[:, :16], Q[:, 16:]


def _cfg(**kw):
    return DcoEngineConfig(kind="lb", d1=16, k=10, capacity=512,
                           query_chunk=8, row_block=512, block_capacity=64,
                           use_kernel=False, **kw)


def _assert_scoped(hlo_text):
    ops = _stage_ops(hlo_text)
    assert {o[1] for o in ops} >= {"dot", "gather"}
    unscoped = [o for o in ops
                if not any(p in SCOPES for p in o[2].split("/"))]
    assert not unscoped, unscoped


def test_screened_scan_names_every_stage_op(tiny):
    st, xs, ql, qt = tiny
    hlo = se._stream_topk_padded.lower(st, xs, ql, qt, {}, None,
                                       _cfg()).compile().as_text()
    _assert_scoped(hlo)
    named = {p for o in _stage_ops(hlo) for p in o[2].split("/")}
    assert named >= {"dco.lead", "dco.compact", "dco.tail", "dco.merge"}
    # the chunk-shared and per-query completions are the two branches of a
    # per-block cond: each compacts and completes under its own scopes, and
    # merges under the merge's
    branches = {}
    for _, _, name in _stage_ops(hlo):
        m = re.search(r"/(branch_\d+)_fun/", "/" + name)
        if m:
            stage = [p for p in name.split("/") if p in SCOPES][-1]
            branches.setdefault(m.group(1), set()).add(stage)
    assert len(branches) == 2, branches
    for stages in branches.values():
        assert stages == {"dco.compact", "dco.tail", "dco.merge"}, stages


def test_forced_full_scan_body_names_every_stage_op(tiny):
    st, xs, ql, qt = tiny
    cfg = _cfg(policy=PolicyConfig(adaptive=True))
    hlo = se._stream_chunk.lower(
        st, xs, ql[:8], qt[:8], {}, None, jnp.ones(8, bool),
        jnp.full(8, 1e9), jnp.zeros(8), cfg, True).compile().as_text()
    _assert_scoped(hlo)
    named = {p for o in _stage_ops(hlo) for p in o[2].split("/")}
    assert named >= {"dco.lead", "dco.tail", "dco.merge"}


def test_seed_names_its_ops(tiny):
    st, xs, ql, qt = tiny
    cfg = _cfg(policy=PolicyConfig(adaptive=True))
    hlo = se._seed_eval.lower(st, xs, ql, qt, {}, cfg).compile().as_text()
    ops = _stage_ops(hlo)
    assert ops and all("dco.seed" in o[2].split("/") for o in ops), ops


def test_one_chip_program_gains_no_scope(tiny):
    """The served one-chip program names the same stages as before the
    mesh's merge had a scope of its own: ``dco.gather`` is the mesh's."""
    st, xs, ql, qt = tiny
    hlo = se._stream_topk_padded.lower(st, xs, ql, qt, {}, None,
                                       _cfg()).compile().as_text()
    assert "dco.gather" not in hlo
    named = {p for o in _stage_ops(hlo) for p in o[2].split("/")
             if p in SCOPES}
    assert named == {"dco.lead", "dco.compact", "dco.tail", "dco.merge",
                     "dco.scan"}


MESH_CHILD = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.jax_engine import (DcoEngineConfig, make_distributed_topk,
                                   shard_stream_blocks)
mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
rng = np.random.default_rng(0)
blocks, shard = shard_stream_blocks(
    rng.standard_normal((1000, 32)).astype(np.float32), 8, 128, mesh,
    ("data",))
cfg = DcoEngineConfig(kind="lb", d1=8, k=10, query_chunk=8, row_block=128,
                      block_capacity=32, use_kernel=False)
fn = jax.jit(make_distributed_topk(mesh, cfg, ("data",), layout=True))
q = jnp.asarray(rng.standard_normal((16, 32)).astype(np.float32))
print(fn.lower(blocks, shard, q[:, :8], q[:, 8:], {}).compile().as_text())
"""


def test_mesh_gather_and_global_merge_carry_their_scope():
    """On four devices the mesh program's ``all_gather`` of each shard's
    top-k, its global ``top_k`` and its ``psum``/``pmin`` all run under
    ``dco.gather``; every other stage op keeps its engine scope."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", MESH_CHILD], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    hlo = proc.stdout
    _assert_scoped(hlo)
    collectives = []
    for line in hlo.splitlines():
        m = INSTR.match(line)
        if m and m.group(2) in ("all-gather", "all-reduce"):
            op = re.search(r'op_name="([^"]*)"', line)
            collectives.append((m.group(2), op.group(1) if op else ""))
    assert {c[0] for c in collectives} == {"all-gather", "all-reduce"}
    assert all(c[1].split("/")[-2] == "dco.gather" for c in collectives), \
        collectives
    merges = [o for o in _stage_ops(hlo)
              if o[2].endswith("top_k") and "_stream_topk_padded" not in o[2]]
    assert len(merges) == 1 and merges[0][2].split("/")[-2] == "dco.gather"
