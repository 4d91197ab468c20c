"""granitehybrid (granite-4.0-h): Mamba2 and NoPE attention layers side by
side, served through ``BatchedEngine(kv_layout="paged")`` with the
attention K/V in the paged pool and each slot's Mamba2 state in the same
``SequenceState`` (``PagedState``).  Checked against the benchmark
family's plain float32 reference (``bench/families/granitehybrid.py``) on
its seeded weights, at a tiny size with both layer kinds present."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench import reference as R  # noqa: E402
from bench.families import granitehybrid as GH  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.core.policy import SpeculativePolicy, ThresholdPolicy  # noqa: E402
from repro.core.scheduler import BatchedEngine  # noqa: E402
from repro.core.seq_state import Lane, PagedState  # noqa: E402
from repro.core.spans import SpanLog  # noqa: E402
from repro.core.speculative import autoregressive_baseline  # noqa: E402
from repro.models import Model  # noqa: E402

SEED = 2**33 + 11
CFG = {"name": "tiny-hmicro", "family": "granitehybrid", "num_layers": 4,
       "layer_types": ["mamba", "attention", "mamba", "mamba"],
       "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
       "d_ff": 128, "vocab_size": 256, "mlp_activation": "silu",
       "use_rope": False, "norm_eps": 1e-5, "tie_embeddings": True,
       "ssm_state": 16, "ssm_head_dim": 16, "ssm_expand": 2,
       "ssm_chunk": 8, "conv_kernel": 4, "embedding_multiplier": 12.0,
       "attention_multiplier": 1 / 16, "residual_multiplier": 0.22,
       "logits_scaling": 8.0, "param_dtype": "float32",
       "activ_dtype": "float32"}
# float32 program against the float32 reference: rounding-level gaps
GAP = 1e-4
BLOCK = 8


@pytest.fixture(scope="module")
def edge():
    """The tiny hybrid in float32 on the family's seeded weights (bf16
    draws, held in float32 as the reference holds them)."""
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          GH.make_on_device(SEED, 0, CFG))
    return Model(ModelConfig(**CFG)), params


@pytest.fixture(scope="module")
def cloud():
    cfg = get_config("smollm-135m").reduced().replace(vocab_size=256)
    m = Model(cfg)
    return m, m.init(jax.random.PRNGKey(1))


def _prompts(lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
            for n in lens]


def _engine(edge, cloud, **kw):
    kw = {"batch_size": 2, "policy": ThresholdPolicy(1.1),
          "use_cache": False, "kv_layout": "paged", "tick_tokens": 4,
          "kv_block_size": BLOCK, **kw}
    return BatchedEngine(edge[0], cloud[0], **kw)


def _gap(prompts, served):
    seqs = [np.concatenate([p, np.asarray(t, np.int32)])
            for p, t in zip(prompts, served)]
    ref = GH.logits_at(CFG, SEED, 0, seqs, [len(p) for p in prompts])
    return R.widest_gap(ref, served)


# ---------------------------------------------------------------- serving
@pytest.mark.parametrize("chunk", [3, 0], ids=["chunked", "whole"])
def test_served_tokens_match_reference(edge, cloud, chunk):
    """Chunked prefill (and whole-prompt admission) through the paged
    engine, slots admitted and retired mid-run: every served token is the
    reference's best at its position."""
    eng = _engine(edge, cloud, prefill_chunk=chunk)
    assert eng.edge.layout == "paged"
    prompts = _prompts([37, 20, 9, 26, 5])
    budgets = [6, 9, 4, 7, 5]
    out = eng.serve_batch(edge[1], cloud[1], prompts, budgets)
    served = [t.tokens for t in out]
    assert [len(t) for t in served] == budgets
    assert all(t.path == "edge" for t in out)
    assert _gap(prompts, served) < GAP


def _chunked_cache(lane, params, prompt, max_seq, chunk):
    job = lane.start_prefill(params, prompt, max_seq, chunk)
    while not lane.advance_prefill(params, job):
        pass
    return job["cache"]


def _prefill_in_row(state, b, params, prompt, chunk):
    """A chunked prefill of ``prompt`` advanced in slot ``b``'s own state
    row and landed; returns the job's detached cache (K/V alone)."""
    assert state.begin(b, prompt, prompt.size + 4)
    job = state.lane.start_prefill(params, prompt,
                                   state.detached_len(prompt.size - 1), chunk)
    while not state.advance_prefill(b, params, job):
        pass
    assert "ssm" not in job["cache"]
    state.finalize(b, job["cache"])
    return job["cache"]


@pytest.mark.parametrize("in_row", [False, True], ids=["detached", "in_row"])
@pytest.mark.parametrize("chunk", [1, 3, 16])
def test_chunked_prefill_matches_one_shot(edge, chunk, in_row):
    """Chunked prefill leaves the same Mamba2 state and K/V as one
    whole-prompt prefill: in a detached cache (the recurrent layout's
    exact-length chunks), or, chunks padded to one length, in a slot's
    own row of the paged state (beside another slot's prefilled row,
    which it leaves alone), with its K/V detached."""
    model, params = edge
    lane = Lane(model, "entropy", 0.0,
                layout="paged" if in_row else "recurrent", block_size=BLOCK)
    prompt = _prompts([30])[0]
    E = prompt.size - 1
    if in_row:
        state = lane.make_state(params, 3, 48)
        other = _prompts([12], seed=4)[0]
        _prefill_in_row(state, 0, params, other, 4)
        before = jax.tree.map(lambda x: np.asarray(x[:, 0]),
                              state.caches["ssm"])
        got = dict(_prefill_in_row(state, 1, params, prompt, chunk))
        rows = state.caches["ssm"]
        got["ssm"] = jax.tree.map(lambda x: x[:, 1:2], rows)
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(rows)):
            np.testing.assert_array_equal(a, np.asarray(b[:, 0]))
    else:
        got = _chunked_cache(lane, params, prompt, 48, chunk)
    _, want = model.prefill(params, {"tokens": jnp.asarray(prompt[None, :-1])},
                            max_seq=48)
    assert int(got["pos"]) == int(want["pos"]) == E
    for a, b in zip(jax.tree.leaves(got["ssm"]), jax.tree.leaves(want["ssm"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)
    for k in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got[k][:, :, :E]),
                                   np.asarray(want[k][:, :, :E]),
                                   rtol=2e-5, atol=2e-5)


def test_paged_step_logits_match_reference(edge):
    """A chunked prefill advances in a slot's own state row and lands its
    K/V blocks; the next paged decode step's logits are the reference's
    at the prompt's last position, and the step leaves the rows of the
    slots it does not mark live as they were."""
    model, params = edge
    lane = Lane(model, "entropy", 0.0, layout="paged", block_size=BLOCK)
    state = lane.make_state(params, 3, 64)
    assert isinstance(state, PagedState)
    prompts = _prompts([29, 17], seed=5)
    for b, p in zip((2, 0), prompts):
        _prefill_in_row(state, b, params, p, 4)
    state.flush()
    tok = np.zeros((3, 1), np.int32)
    tok[2, 0], tok[0, 0] = prompts[0][-1], prompts[1][-1]
    state.prepare_tick([0, 2], np.array([1, 0, 1]), 1)
    rows = jax.tree.map(np.asarray, state.caches["ssm"])
    live = jnp.asarray([True, False, True])
    lg, out = model.paged_decode_step(params, jnp.asarray(tok), state.caches,
                                      live=live)
    for a, b in zip(jax.tree.leaves(rows), jax.tree.leaves(out["ssm"])):
        np.testing.assert_array_equal(a[:, 1], np.asarray(b[:, 1]))
        assert not np.array_equal(a[:, 2], np.asarray(b[:, 2]))
    # logits_at gives the logits at start - 1 .. len - 2: read the last
    # position through a one-token continuation
    ref = GH.logits_at(CFG, SEED, 0, [np.append(p, 0) for p in prompts],
                       [p.size for p in prompts])
    np.testing.assert_allclose(np.asarray(lg[2]), ref[0][0], atol=2e-5)
    np.testing.assert_allclose(np.asarray(lg[0]), ref[1][0], atol=2e-5)


def test_readmitted_slot_starts_from_its_own_prefill(edge, cloud):
    """One slot serves three requests in turn: each gets the tokens it
    gets alone, not a continuation of the previous occupant's state."""
    prompts = _prompts([21, 13, 30], seed=7)
    budgets = [7, 5, 6]
    eng = _engine(edge, cloud, batch_size=1, prefill_chunk=4)
    together = [t.tokens for t in eng.serve_batch(edge[1], cloud[1],
                                                  prompts, budgets)]
    alone = [_engine(edge, cloud, batch_size=1, prefill_chunk=4).serve_batch(
        edge[1], cloud[1], [p], [m])[0].tokens
        for p, m in zip(prompts, budgets)]
    assert together == alone
    assert _gap(prompts, together) < GAP


def test_state_commit_spans_and_rows(edge, cloud):
    """Each chunked finalize opens a ``state.commit`` span with the row's
    bytes; each decoding tick counts the state rows its steps move."""
    eng = _engine(edge, cloud, prefill_chunk=4)
    eng.spans = log = SpanLog()
    prompts = _prompts([18, 11, 25])
    eng.serve_batch(edge[1], cloud[1], prompts, [3, 4, 5])
    commits = [s for s in log.spans if s.name == "state.commit"]
    cfg = eng.edge_model.cfg
    # every Mamba2 layer's S, normaliser and log-max, and its conv inputs
    di = cfg.ssm_expand * cfg.d_model
    H, N, P = di // cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_head_dim
    row = 4 * cfg.layers_of("mamba") * (
        H * N * P + H * N + H + (cfg.conv_kernel - 1) * (di + 2 * N))
    assert len(commits) == len(prompts)
    assert all(s.counts == {"rows": 1, "bytes": row} for s in commits)
    assert {s.rid for s in commits} == {0, 1, 2}
    disp = [s for s in log.spans if s.name == "edge.dispatch"]
    assert disp and all(s.counts["state_rows"] == 2 for s in disp)


def test_table_and_state_updates_compile_once(edge, cloud, compile_counter):
    """A prefill chunk in a slot's state row and a finished prefill's K/V
    blocks run one fixed-shape program each, whatever the slot or the
    prompt's length (five prompts into four slots, one compile each), and
    a second drain of the same shapes compiles nothing."""
    eng = _engine(edge, cloud, batch_size=4, prefill_chunk=4)
    prompts = _prompts([18, 11, 25, 14, 20], seed=9)
    first = eng.serve_batch(edge[1], cloud[1], prompts, [3, 4, 5, 2, 3])
    for prog in ("extend_row", "write_pool_blocks"):
        got = [e for e in compile_counter.events if prog in e]
        assert len(got) == 1, got
    n0 = compile_counter.count
    again = eng.serve_batch(edge[1], cloud[1], prompts, [3, 4, 5, 2, 3])
    assert compile_counter.count == n0, compile_counter.events[n0:]
    assert [t.tokens for t in first] == [t.tokens for t in again]


# ---------------------------------------------------------------- refusals
def test_escalation_refused_across_vocabularies(edge):
    """An edge and cloud with different vocabularies build an engine, and a
    request that would escalate is refused naming both sizes."""
    cm = Model(get_config("smollm-135m").reduced().replace(vocab_size=512))
    cp = cm.init(jax.random.PRNGKey(1))
    eng = BatchedEngine(edge[0], cm, batch_size=2, use_cache=False,
                        kv_layout="paged", kv_block_size=BLOCK,
                        policy=ThresholdPolicy(-1.0))
    with pytest.raises(ValueError, match="vocab_size 256.*vocab_size 512"):
        eng.serve_batch(edge[1], cp, _prompts([9]), [3])
    # nothing escalates: served
    eng = BatchedEngine(edge[0], cm, batch_size=2, use_cache=False,
                        kv_layout="paged", kv_block_size=BLOCK,
                        policy=ThresholdPolicy(1.1))
    assert len(eng.serve_batch(edge[1], cp, _prompts([9]), [3])[0].tokens) \
        == 3


def test_speculation_refused_on_unrewindable_state(edge, cloud):
    """Speculation rewinds the draft's state by a ``pos`` write on the
    paged layout, which recurrent rows cannot take: refused where a
    request would escalate, naming the family."""
    eng = _engine(edge, cloud, policy=SpeculativePolicy(-1.0))
    assert not eng.spec.rewindable
    with pytest.raises(ValueError, match="granitehybrid"):
        eng.serve_batch(edge[1], cloud[1], _prompts([9]), [3])


def test_recurrent_layout_speculation_lossless(edge, cloud):
    """Off the paged layout the hybrid's cache is a dense K/V slab beside
    its state (the ``recurrent`` layout), which speculation rewinds by
    replaying the accepted prefix (``replay_step``): greedy speculative
    escalation equals the cloud's own greedy decoding."""
    eng = BatchedEngine(edge[0], cloud[0], batch_size=2, temperature=0.0,
                        policy=SpeculativePolicy(-1.0), use_cache=False,
                        kv_layout="dense")
    assert eng.edge.layout == "recurrent" and eng.spec.rewindable
    prompts = _prompts([8, 6])
    for p, t in zip(prompts, eng.serve_batch(edge[1], cloud[1], prompts, 8)):
        assert t.path == "speculative"
        assert t.tokens == autoregressive_baseline(cloud[0], cloud[1], p, 8,
                                                   temperature=0.0)


def test_replay_step_lands_on_the_decoded_prefix(edge):
    """``replay_step(tape, count)`` leaves the Mamba2 state and K/V that
    decoding ``tape[:count]`` one token at a time leaves, for every count
    (0 keeps the cache)."""
    model, params = edge
    _, cache = model.prefill(params, {"tokens": jnp.asarray(_prompts([6]))},
                             max_seq=16)
    tape = jnp.asarray([[7, 11, 13, 17]], jnp.int32)
    for count in range(tape.shape[1] + 1):
        got = model.replay_step(params, tape, cache, jnp.int32(count))
        want = cache
        for t in range(count):
            _, want = model.decode_step(params, tape[:, t:t + 1], want)
        assert int(got["pos"]) == int(want["pos"])
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"count={count}")


def test_paged_zamba2_hybrid_still_refused():
    cfg = get_config("zamba2-2.7b").reduced()
    dense = Model(get_config("smollm-135m").reduced().replace(
        vocab_size=cfg.vocab_size))
    with pytest.raises(ValueError, match="paged"):
        BatchedEngine(Model(cfg), dense, kv_layout="paged")


# ---------------------------------------------------------------- config
def test_layer_types_survive_json_and_hash():
    cfg = ModelConfig(**json.loads(json.dumps(CFG)))
    assert cfg.layer_types == ("mamba", "attention", "mamba", "mamba")
    assert hash(cfg) == hash(ModelConfig(**CFG))
    assert cfg.layers_of("attention") == 1 and not cfg.use_rope
    with pytest.raises(ValueError, match="layer_types"):
        ModelConfig(**dict(CFG, num_layers=3))


def test_reduced_keeps_both_kinds():
    full = get_config("granite-4.0-h-micro")
    small = full.reduced()
    assert set(small.layer_types) == {"mamba", "attention"}
    assert small.num_layers == len(small.layer_types)
    assert small.n_params() < full.n_params()
    # the published model and its cuts agree on the block's parameters
    assert GH._matmul_params({**CFG}) < ModelConfig(**CFG).n_params()
