"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the program's batch search
(``JaxBackend._search``), under the service that the window drives, and a
whole run of a cell follows on the CPU at a tiny size.  The cells are on
one chip, so the fault of an exchange between chips left out has no place
here."""
import bench_testutil
import numpy as np
import pytest

from bench.run import run_cell
from repro.api.backends import JaxBackend

SEED = 987654321
_search = JaxBackend._search


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return bench_testutil.tiny_root(tmp_path_factory.mktemp("tiny"))


def stale(self, Q, k, **kw):
    """The step hands back the state it had: the batch before's answers."""
    out = _search(self, Q, k, **kw)
    prev = getattr(self, "_stale", out)
    self._stale = out
    return prev


def half_batch(self, Q, k, **kw):
    """Half of the batch left out: its rows (the first half, where the
    service puts real requests before the padding) carry the other half's
    answers."""
    d, i, stats = _search(self, Q, k, **kw)
    h = len(Q) // 2
    d, i = d.copy(), i.copy()
    d[:h], i[:h] = d[len(Q) - h:], i[len(Q) - h:]
    return d, i, stats


def altered(self, Q, k, **kw):
    """One answer altered where it is produced: the first query's nearest
    id replaced by the next row."""
    d, i, stats = _search(self, Q, k, **kw)
    i = i.copy()
    i[0, 0] = (i[0, 0] + 1) % self.method.state["N"]
    return d, i, stats


@pytest.mark.parametrize("fault", [stale, half_batch, altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", [
                                  "laion512-2m.ood-bulk",
                                  "wiki768-1m.id-bulk"])
def test_fault_is_not_correct(tiny, monkeypatch, cell, fault):
    monkeypatch.setattr(JaxBackend, "_search", fault)
    out = run_cell(tiny, cell, SEED, 1.0, False, require_tpu=False,
                   cache=False)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["wrong_ranks"]["value"] > 0
    assert np.isfinite(out["checks"]["dist_err_ulps"]["value"])


def test_a_batch_that_raises_is_failed_not_a_crash(tiny, monkeypatch):
    """Every step raising: nothing is served, every window request
    resolves ``failed``, and the run still prints a result."""
    def broken(self, Q, k, **kw):
        raise RuntimeError("planted")
    monkeypatch.setattr(JaxBackend, "_search", broken)
    out = run_cell(tiny, "wiki768-1m.id-bulk", SEED, 1.0, False,
                   require_tpu=False, cache=False)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] > 0
