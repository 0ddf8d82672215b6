"""The adaptive policy's forced full-scan body, served once per batch
(``core.stream_engine._dispatch``, DESIGN.md §5).

Every chunk the seed (or ``force_fallback``) sends to the full-scan body is
served by ONE ``_stream_chunk(..., forced=True)`` call whose tile holds all
of those chunks' queries, padded with zero queries to a power-of-two number
of chunks.  Its answers, ``fallback_blocks``, ``rule_timeline`` and the
dispatch span's ``full_chunks`` equal those of the per-chunk calls, chunk by
chunk, and the ids equal a float64 brute force."""
import contextlib
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stream_engine as se
from repro.core.jax_engine import DcoEngineConfig
from repro.core.policy import PolicyConfig

K, D, D1, C = 10, 64, 16, 8


@pytest.fixture(scope="module")
def corpus():
    """A corpus whose energy decays over its dims, so that the lead dims
    screen queries drawn near its rows (the seed keeps their chunks on the
    switching scan) and not queries whose energy sits in the last dims (the
    seed forces their chunks)."""
    rng = np.random.default_rng(0)
    std = (3.0 * np.exp(-np.arange(D) / 8.0)).astype(np.float32)
    X = (rng.standard_normal((3000, D)) * std).astype(np.float32)
    st = {"x_lead": jnp.asarray(X[:, :D1]), "x_tail": jnp.asarray(X[:, D1:]),
          "lead_sq": jnp.asarray((X[:, :D1] ** 2).sum(1)),
          "tail_sq": jnp.asarray((X[:, D1:] ** 2).sum(1))}
    q_id = (X[rng.choice(len(X), 4 * C)]
            + 0.05 * rng.standard_normal((4 * C, D))).astype(np.float32)
    q_ood = (rng.standard_normal((4 * C, D)) * std[::-1]).astype(np.float32)
    return types.SimpleNamespace(X=X, st=st, q_id=q_id, q_ood=q_ood)


def _cfg(**policy):
    return DcoEngineConfig(kind="lb", d1=D1, k=K, query_chunk=C,
                           row_block=512, block_capacity=64,
                           use_kernel=False, policy=PolicyConfig(**policy))


def _brute(X, Q):
    d2 = ((Q.astype(np.float64)[:, None, :]
           - X.astype(np.float64)[None]) ** 2).sum(-1)
    return np.argsort(d2, axis=1)[:, :K]


def _batch(corpus, kinds, nq=None):
    """Queries chunk by chunk: ``"ood"`` or ``"id"`` per chunk of C rows,
    cut to ``nq`` rows."""
    Q = np.concatenate([getattr(corpus, "q_" + kind)[i * C:(i + 1) * C]
                        for i, kind in enumerate(kinds)])
    return Q[:nq]


@pytest.fixture
def calls(monkeypatch):
    """Every ``_stream_chunk`` call of the dispatch as (rows, forced), and
    the counters of its ``search.dispatch`` span."""
    log = types.SimpleNamespace(chunks=[], dispatch={})
    real = se._stream_chunk

    def counted(state, xs, ql, *args):
        log.chunks.append((ql.shape[0], args[-1]))
        return real(state, xs, ql, *args)

    @contextlib.contextmanager
    def recorded(name, **args):
        sp = types.SimpleNamespace(set_metadata=(
            log.dispatch.update if name == "search.dispatch"
            else lambda **kw: None))
        yield sp

    monkeypatch.setattr(se, "_stream_chunk", counted)
    monkeypatch.setattr(se, "span", recorded)
    return log


def _run(corpus, Q, cfg):
    ql, qt = jnp.asarray(Q[:, :D1]), jnp.asarray(Q[:, D1:])
    return se.stream_topk(corpus.st, ql, qt, cfg)


def _per_chunk(corpus, Q, cfg, forced):
    """The batch served one ``_stream_chunk`` call per chunk, as the seed
    (when the policy has one) and ``forced`` route each chunk."""
    nq = Q.shape[0]
    Qp = np.pad(Q, ((0, (-nq) % C), (0, 0)))
    ql, qt = jnp.asarray(Qp[:, :D1]), jnp.asarray(Qp[:, D1:])
    blocks = se.build_stream_blocks(corpus.st, cfg.row_block)
    tau0 = ew0 = None
    if not cfg.policy.force_fallback:
        tau0, ew0 = se._seed_eval(corpus.st, blocks, ql, qt, {}, cfg)
    qv = jnp.arange(Qp.shape[0]) < nq
    outs = []
    for ci, f in enumerate(forced):
        sl = slice(ci * C, (ci + 1) * C)
        outs.append(se._stream_chunk(
            corpus.st, blocks, ql[sl], qt[sl], {}, None, qv[sl],
            None if tau0 is None else tau0[sl],
            None if ew0 is None else ew0[sl], cfg, bool(f)))
    res = [np.concatenate([np.asarray(o[j]) for o in outs])[:nq]
           for j in range(7)]
    fb = np.concatenate([np.asarray(o[7]["fb"]) for o in outs])[:nq]
    timeline = np.stack([np.asarray(o[7]["timeline"]) for o in outs]).mean(0)
    return res, fb, timeline


def _assert_same(corpus, Q, out, ref):
    (d, i, *_), rep = out[:7], out[7]
    res, fb, timeline = ref
    np.testing.assert_array_equal(np.asarray(i), _brute(corpus.X, Q))
    np.testing.assert_array_equal(np.asarray(i), res[1])
    np.testing.assert_allclose(np.asarray(d), res[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(rep["fallback_blocks"]), fb)
    np.testing.assert_allclose(np.asarray(rep["rule_timeline"]), timeline,
                               rtol=1e-6)
    assert not np.isfinite(np.asarray(out[4])[np.asarray(
        rep["fallback_blocks"]) > 0]).any()     # forced: certified


@pytest.mark.parametrize("policy", [dict(force_fallback=True), {}],
                         ids=["force_fallback", "seed"])
def test_all_forced_batch_is_one_call_of_every_query(corpus, calls, policy):
    Q = _batch(corpus, ["ood"] * 4)
    cfg = _cfg(**policy)
    out = _run(corpus, Q, cfg)
    assert calls.chunks == [(4 * C, True)]
    assert calls.dispatch == {"chunks": 4, "full_chunks": 4}
    assert out[7]["full_scan_tile"] == 4 * C
    _assert_same(corpus, Q, out, _per_chunk(corpus, Q, cfg, [True] * 4))
    nb = -(-len(corpus.X) // cfg.row_block)
    assert (np.asarray(out[7]["fallback_blocks"]) == nb).all()
    assert (np.asarray(out[7]["rule_timeline"]) == 1.0).all()


def test_mixed_batch_keeps_every_answer_in_place(corpus, calls):
    """The seed forces chunks 0 and 2 of 4: they share one call of two
    chunks, chunks 1 and 3 keep a switching call each, and every query's
    answer comes back at its own row."""
    Q = _batch(corpus, ["ood", "id", "ood", "id"])
    cfg = _cfg()
    out = _run(corpus, Q, cfg)
    assert calls.chunks == [(2 * C, True), (C, False), (C, False)]
    assert calls.dispatch == {"chunks": 4, "full_chunks": 2}
    assert out[7]["full_scan_tile"] == 2 * C
    fb = np.asarray(out[7]["fallback_blocks"]).reshape(4, C)
    assert (fb[[0, 2]] > 0).all() and (fb[[1, 3]] == 0).all()
    _assert_same(corpus, Q, out,
                 _per_chunk(corpus, Q, cfg, [True, False, True, False]))


def test_three_forced_chunks_run_as_one_call_padded_to_four(corpus, calls):
    """Three forced chunks take one call at four chunks' rows; the
    rule_timeline weighs that call as the three chunks it served, beside
    the one switching chunk."""
    Q = _batch(corpus, ["ood", "id", "ood", "ood"])
    cfg = _cfg()
    out = _run(corpus, Q, cfg)
    assert calls.chunks == [(4 * C, True), (C, False)]
    assert calls.dispatch == {"chunks": 4, "full_chunks": 3}
    assert out[7]["full_scan_tile"] == 4 * C
    ref = _per_chunk(corpus, Q, cfg, [True, False, True, True])
    _assert_same(corpus, Q, out, ref)
    assert (np.asarray(out[7]["rule_timeline"]) == 0.75).all()


@pytest.mark.parametrize("kinds,nq,tile", [
    (["ood"] * 4, 3 * C + 3, 4 * C),
    (["ood", "id", "ood"], 2 * C + 4, 2 * C)], ids=["all-forced", "mixed"])
def test_ragged_batch_stays_correct(corpus, calls, kinds, nq, tile):
    """A batch whose last chunk is part padding: the padded rows ride in
    the tile and are dropped from every output."""
    Q = _batch(corpus, kinds, nq)
    cfg = _cfg()
    out = _run(corpus, Q, cfg)
    assert out[7]["full_scan_tile"] == tile
    assert all(np.asarray(a).shape[0] == nq for a in out[:7])
    assert np.asarray(out[7]["fallback_blocks"]).shape == (nq,)
    _assert_same(corpus, Q, out,
                 _per_chunk(corpus, Q, cfg, [k == "ood" for k in kinds]))


def test_no_forced_chunk_reads_a_zero_tile(corpus, calls):
    Q = _batch(corpus, ["id"] * 2)
    out = _run(corpus, Q, _cfg())
    assert calls.chunks == [(C, False), (C, False)]
    assert out[7]["full_scan_tile"] == 0
    np.testing.assert_array_equal(np.asarray(out[1]), _brute(corpus.X, Q))


def test_facade_reports_full_scan_tile(corpus, monkeypatch):
    """``full_scan_tile`` is a stat of the facade and an arg of its
    ``search.finish`` span."""
    from repro.api import SchedulePolicy, open_index
    from repro.api import backends
    from repro.vecdata.synthetic import make_ood_queries
    finish = {}
    real = backends.span

    @contextlib.contextmanager
    def recorded(name, **args):
        with real(name, **args) as sp:
            if name != "search.finish":
                yield sp
                return

            def set_metadata(**kw):
                finish.update(kw)
                sp.set_metadata(**kw)
            yield types.SimpleNamespace(set_metadata=set_metadata)

    monkeypatch.setattr(backends, "span", recorded)
    sess = open_index(corpus.X, method="PDScanning+", backend="jax",
                      schedule=SchedulePolicy(d1=D1, query_chunk=C,
                                              row_block=512,
                                              block_capacity=64,
                                              adaptive=True))
    res = sess.search(make_ood_queries(corpus.X, 4 * C, severity=1.0), K)
    assert res.stats.extra["full_scan_tile"] == 4 * C
    assert finish["full_scan_tile"] == 4 * C
