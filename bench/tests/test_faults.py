"""A run with the timed path broken underneath reports ``correct`` false:
the harness minus its look for a chip, on the tiny configuration, with a
fault planted in the program for each fault the cells can have."""
import pytest

from bench.tests import _tiny


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def _bump(x, vocab):
    return (x + 1) % vocab


def test_edge_token_altered_where_produced(monkeypatch):
    """The edge decode tick hands the host a token one off from the one
    it computed (the carry keeps the computed one)."""
    from repro.core import seq_state
    init = seq_state.Lane.__init__

    def broken(self, *a, **k):
        init(self, *a, **k)
        chunk, vocab = self._chunk, self.model.cfg.vocab_size

        def tick(*args, **kw):
            out = chunk(*args, **kw)
            return out[:4] + (_bump(out[4], vocab),) + out[5:]
        self._chunk = tick
    monkeypatch.setattr(seq_state.Lane, "__init__", broken)
    res = _tiny.run("chat", 5)
    assert not res["correct"]
    assert res["checks"]["edge_gap"]["value"] > \
        res["checks"]["edge_gap"]["limit"]


def test_speculation_token_altered_where_produced(monkeypatch):
    """The speculation round emits a token one off from the cloud's."""
    from repro.core import speculative
    init = speculative.BatchedSpecDecoder.__init__

    def broken(self, draft_model, target_model, **k):
        init(self, draft_model, target_model, **k)
        rnd, vocab = self._round, target_model.cfg.vocab_size

        def round_(*args):
            out = rnd(*args)
            return out[:5] + (_bump(out[5], vocab),)
        self._round = round_
    monkeypatch.setattr(speculative.BatchedSpecDecoder, "__init__", broken)
    res = _tiny.run("esc", 6)
    assert not res["correct"]
    assert res["checks"]["cloud_gap"]["value"] > \
        res["checks"]["cloud_gap"]["limit"]


def test_half_the_requests_left_out(monkeypatch):
    """Half of a drain's requests never come back."""
    from repro.core.scheduler import BatchedEngine
    run = BatchedEngine.run

    def broken(self, *a, **k):
        res = run(self, *a, **k)
        return {rid: tr for i, (rid, tr) in enumerate(sorted(res.items()))
                if i % 2 == 0}
    monkeypatch.setattr(BatchedEngine, "run", broken)
    res = _tiny.run("chat", 7)
    assert not res["correct"] and res["failed"] > 0
