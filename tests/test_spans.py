"""Spans and counters of the drain loop (``core/spans.py``).

Off (the default) the engine records nothing and builds no profiler
annotation.  On, every drain-loop iteration is one ``engine.tick`` span
whose steps lie inside it in loop order, the tick counters add up to the
tokens the edge slots emitted, the per-request stamps come in lifecycle
order, and a warmed drain stays free of recompiles and implicit pulls.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import SpeculativePolicy, ThresholdPolicy
from repro.core.scheduler import BatchedEngine
from repro.core.spans import SpanLog
from repro.models import Model

STEPS = ("sched.admit", "prefill.advance", "kv.flush", "kv.prepare_tick",
         "edge.dispatch", "edge.readback", "sched.retire")
B, TICK = 2, 4                      # prompts over TICK entries prefill chunked
LENS = [13, 9, 5, 11, 3]
BUDGETS = [6, 9, 3, 7, 5]


@pytest.fixture(scope="module")
def pair():
    e_cfg = get_config("smollm-135m").reduced()
    c_cfg = get_config("granite-8b").reduced().replace(
        vocab_size=e_cfg.vocab_size)
    edge, cloud = Model(e_cfg), Model(c_cfg)
    return (edge, edge.init(jax.random.PRNGKey(0)),
            cloud, cloud.init(jax.random.PRNGKey(1)))


@pytest.fixture(scope="module")
def prompts(pair):
    vocab = pair[0].cfg.vocab_size
    return [((np.arange(n) * 7 + 5 * i) % vocab).astype(np.int32)
            for i, n in enumerate(LENS)]


@pytest.fixture
def annotations(monkeypatch):
    """Names of every profiler annotation built while the test runs."""
    names = []
    real = jax.profiler.TraceAnnotation

    class Counted(real):
        def __init__(self, name, **kw):
            names.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counted)
    return names


def _engine(pair, policy, spans=None):
    edge, _, cloud, _ = pair
    return BatchedEngine(edge, cloud, batch_size=B, temperature=0.0,
                         policy=policy, use_cache=False, tick_tokens=TICK,
                         spans=spans)


def _serve(eng, pair, prompts):
    _, ep, _, cp = pair
    return eng.serve_batch(ep, cp, prompts, BUDGETS)


@pytest.fixture(scope="module")
def edge_run(pair, prompts):
    """One edge-only drain with spans off, then the same drain with spans
    on, through one engine: (engine, off traces, on traces, span log)."""
    eng = _engine(pair, ThresholdPolicy(1.1))
    off = _serve(eng, pair, prompts)
    log = eng.spans = SpanLog()
    on = _serve(eng, pair, prompts)
    eng.spans = None
    return eng, off, on, log


def _ticks(log):
    """(tick, its steps) for every top-level span; steps have no steps."""
    spans = log.spans
    assert all(s.parent is None or spans[s.parent].parent is None
               for s in spans)
    return [(s, [c for c in spans if c.parent == i])
            for i, s in enumerate(spans) if s.parent is None]


def test_spans_off_build_nothing(pair, prompts, annotations):
    eng = _engine(pair, ThresholdPolicy(1.1))
    assert eng.spans is None
    _serve(eng, pair, prompts)
    assert annotations == []


def test_tick_spans_nest_in_loop_order(edge_run, annotations, pair,
                                       prompts):
    eng, off, on, log = edge_run
    assert [t.tokens for t in on] == [t.tokens for t in off]
    ticks = _ticks(log)
    assert ticks and all(t.name == "engine.tick" for t, _ in ticks)
    dispatched = 0
    for tick, kids in ticks:
        names = tuple(k.name for k in kids)
        # a tick that stops early ran a prefix of the steps
        assert names == STEPS[:len(names)] and len(names) >= 2
        dispatched += names == STEPS
        t = tick.start_ms
        for k in kids:
            assert tick.start_ms <= k.start_ms <= k.end_ms <= tick.end_ms
            assert k.start_ms >= t          # one after another
            t = k.end_ms
    assert dispatched > 0
    # on, each span holds one annotation of its own name
    eng.spans = log2 = SpanLog()
    try:
        _serve(eng, pair, prompts)
    finally:
        eng.spans = None
    assert annotations == [s.name for s in log2.spans]


def test_tick_counters_add_up(edge_run):
    eng, _, on, log = edge_run
    assert all(t.path == "edge" for t in on)
    ticks = _ticks(log)
    active = run = 0
    for tick, kids in ticks:
        c = tick.counts
        disp = [k for k in kids if k.name == "edge.dispatch"]
        if not disp:
            assert c == {}
            continue
        assert c["slot_steps_run"] == disp[0].counts["n_steps"] * B
        assert 0 <= c["slots_decoding"] <= c["slots_occupied"] <= B
        active += c["slot_steps_active"]
        run += c["slot_steps_run"]
    assert active == sum(len(t.tokens) for t in on) == sum(BUDGETS)
    assert active <= run
    # chunked prefill: one chunk per job per tick, every entry once
    pre = [k for _, kids in ticks for k in kids
           if k.name == "prefill.advance"]
    chunked = [n for n in LENS if n - 1 > TICK]
    assert sum(k.counts.get("tokens", 0) for k in pre) == \
        sum(n - 1 for n in chunked)
    assert sum(k.counts["jobs"] for k in pre) == \
        sum(-(-(n - 1) // TICK) for n in chunked)


def _edge_states(eng, monkeypatch):
    """Every decode state the engine's edge lane builds from now on."""
    made = []
    real = eng.edge.make_state

    def make_state(*a, **kw):
        made.append(real(*a, **kw))
        return made[-1]
    monkeypatch.setattr(eng.edge, "make_state", make_state)
    return made


def test_attn_block_counters_nest(edge_run):
    """live <= visited <= table on every decoding tick; each emitting
    slot-step walks at least one block."""
    eng, _, _, log = edge_run
    ticks = [(t, kids) for t, kids in _ticks(log)
             if any(k.name == "edge.dispatch" for k in kids)]
    assert ticks
    for tick, kids in ticks:
        c = tick.counts
        n = next(k for k in kids if k.name == "edge.dispatch").counts[
            "n_steps"]
        assert c["attn_blocks_table"] % (n * B) == 0
        assert c["slot_steps_active"] <= c["attn_blocks_live"] \
            <= c["attn_blocks_visited"] <= c["attn_blocks_table"]
    assert any(t.counts["attn_blocks_visited"] < t.counts["attn_blocks_table"]
               for t, _ in ticks)


@pytest.mark.parametrize("spans", [False, True], ids=["spans_off",
                                                      "spans_on"])
def test_pos_mirror_matches_device(pair, prompts, monkeypatch, spans):
    """After a drain the host mirror of ``pos`` equals the device's (read
    here, once; the engine never pulls it).  Spans off, no walk is
    counted at all."""
    from repro.core.seq_state import PagedKV
    calls = []
    real = PagedKV.attn_blocks
    monkeypatch.setattr(PagedKV, "attn_blocks",
                        lambda self, *a: calls.append(a) or real(self, *a))
    eng = _engine(pair, ThresholdPolicy(1.1),
                  spans=SpanLog() if spans else None)
    made = _edge_states(eng, monkeypatch)
    _serve(eng, pair, prompts)
    state, = made
    assert isinstance(state, PagedKV)
    np.testing.assert_array_equal(state.pos_h,
                                  np.asarray(state.caches["pos"]))
    if spans:
        assert len(calls) == sum(
            "attn_blocks_table" in s.counts for s in eng.spans.spans) > 0
    else:
        assert calls == []
    # a 2-step tick: slot 0 attends bs then bs + 1 keys (one block, then
    # two), slot 1 runs past its table (MB each step); slot 1 masked in
    # the first step
    bs, MB = state.block_size, state.max_blocks
    state.pos_h[:] = [bs + 1, MB * bs + 7]
    assert real(state, 2, np.array([[True, False], [True, True]])) == {
        "attn_blocks_table": 4 * MB, "attn_blocks_visited": 3 + 2 * MB,
        "attn_blocks_live": 3 + MB}


@pytest.mark.parametrize("policy", ["edge", "escalated"])
def test_request_stamps_in_lifecycle_order(pair, prompts, edge_run, policy):
    if policy == "edge":
        eng = edge_run[0]
    else:
        eng = _engine(pair, SpeculativePolicy(-1.0), spans=SpanLog())
        traces = _serve(eng, pair, prompts)
        assert all(t.path == "speculative" for t in traces)
    for n, ev in zip(LENS, eng.events.values()):
        assert ev["admit_ms"] <= ev["prefill_done_ms"] \
            <= ev["first_token_ready_ms"] <= ev["retire_ms"]
        assert ev["first_token_ms"] <= ev["first_token_ready_ms"]
        if n - 1 > TICK:
            assert ev["prefill_chunks"] == -(-(n - 1) // TICK)
        else:
            assert ev["prefill_done_ms"] == ev["admit_ms"]
            assert "prefill_chunks" not in ev
        if policy == "escalated":
            assert ev["first_token_ready_ms"] == ev["retire_ms"]


def test_steady_state_drain_with_spans_is_pure(pair, prompts,
                                                compile_counter):
    """The spans add no compile and no implicit device pull."""
    eng = _engine(pair, ThresholdPolicy(1.1), spans=SpanLog())
    warm = _serve(eng, pair, prompts)
    compile_counter.reset()
    with jax.transfer_guard_device_to_host("disallow"):
        steady = _serve(eng, pair, prompts)
    assert compile_counter.count == 0, (
        "steady-state drain recompiled: " + "; ".join(compile_counter.events))
    assert [t.tokens for t in warm] == [t.tokens for t in steady]
