"""Family-agnostic SequenceState serving: batched recurrent speculation.

The contract extended to every model family: ``BatchedEngine`` routes
recurrent-state edge/cloud models (ssm = mamba2, hybrid = zamba2, xlstm)
through the SAME slot/tick/grouped-escalation machinery as the KV families,
with speculative rewinds executed as batched accepted-prefix replays
(``Model.replay_step`` behind ``core/seq_state.py``) — token-for-token
equal to ``serve_reference``'s per-request snapshot+replay loop, with ZERO
host-side per-request fallback calls.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import speculative as spec_mod
from repro.configs import get_config
from repro.core.engine import CollaborativeEngine
from repro.core.policy import (SpeculativePolicy, ThresholdPolicy,
                               policy_from_legacy)
from repro.core.scheduler import BatchedEngine
from repro.core.seq_state import PagedKV, layout_for
from repro.core.speculative import autoregressive_baseline
from repro.models import Model

# one edge arch per family named by the acceptance criteria; the shared
# cloud is the dense transformer (mixed family pairs by construction)
EDGE_ARCHS = {
    "dense": "smollm-135m",
    "moe": "granite-moe-1b-a400m",
    "ssm": "mamba2-370m",
    "hybrid": "zamba2-2.7b",
    "xlstm": "xlstm-125m",
}
RECURRENT = ("ssm", "hybrid", "xlstm")


@pytest.fixture(scope="module")
def cloud():
    c_cfg = get_config("granite-8b").reduced().replace(vocab_size=512)
    m = Model(c_cfg)
    return m, m.init(jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def edges():
    out = {}
    for fam, arch in EDGE_ARCHS.items():
        cfg = get_config(arch).reduced().replace(vocab_size=512)
        m = Model(cfg)
        out[fam] = (m, m.init(jax.random.PRNGKey(0)))
    return out


def _prompts(vocab, specs):
    return [((np.arange(n) * 7 + off) % vocab).astype(np.int32)
            for n, off in specs]


# ---------------------------------------------------------------- routing
def test_recurrent_layouts_resolved(edges, cloud):
    """Recurrent families get the recurrent adapter; the mixed pair's cloud
    lane keeps its KV layout; nobody needs a per-request fallback."""
    cm, _ = cloud
    for fam in RECURRENT:
        em, _ = edges[fam]
        be = BatchedEngine(em, cm, use_cache=False)
        assert be.kv_layout == "dense"          # auto: no paging for state
        assert be.edge.layout == "recurrent"
        assert be.cloud.layout == "dense"
        assert layout_for(em, be.kv_layout) == "recurrent"


# ---------------------------------------------------------------- edge path
@pytest.mark.parametrize("fam", RECURRENT)
def test_recurrent_edge_parity_staggered(fam, edges, cloud):
    """Greedy tokens match serve_reference under staggered prompt lengths
    AND budgets, with a batch smaller than the request count so slots
    admit/retire mid-run."""
    em, ep = edges[fam]
    cm, cp = cloud
    prompts = _prompts(512, [(8, 0), (6, 3), (9, 7), (5, 2)])
    budgets = [3, 9, 6, 8]
    ref = CollaborativeEngine(em, cm, temperature=0.0,
                              policy=ThresholdPolicy(1.1), use_cache=False)
    be = BatchedEngine(em, cm, batch_size=2, temperature=0.0,
                       policy=ThresholdPolicy(1.1), use_cache=False,
                       tick_tokens=4)
    bts = be.serve_batch(ep, cp, prompts, budgets)
    for p, m, bt in zip(prompts, budgets, bts):
        rt = ref.serve_reference(ep, cp, p, m)
        assert bt.path == rt.path == "edge"
        assert bt.tokens == rt.tokens and len(bt.tokens) == m
        assert abs(bt.uncertainty - rt.uncertainty) < 1e-5


# ---------------------------------------------------------------- chunked
@pytest.mark.parametrize("fam", EDGE_ARCHS)
def test_chunked_prefill_parity(fam, edges, cloud):
    """Long prompts admitted via DETACHED CHUNKED PREFILL (prefill_chunk=8
    entries landing across ticks, interleaved with the batch's decode)
    keep exact greedy token parity with ``serve_reference`` on EVERY
    family.  Lengths straddle the chunk size: an exact multiple (33 ->
    32 entries), above/below multiples (21, 16), and one short prompt (9)
    that takes the unchunked whole-prompt path alongside the jobs."""
    em, ep = edges[fam]
    cm, cp = cloud
    prompts = _prompts(512, [(33, 0), (21, 5), (16, 9), (9, 2)])
    budgets = [6, 4, 7, 5]
    ref = CollaborativeEngine(em, cm, temperature=0.0,
                              policy=ThresholdPolicy(1.1), use_cache=False)
    be = BatchedEngine(em, cm, batch_size=2, temperature=0.0,
                       policy=ThresholdPolicy(1.1), use_cache=False,
                       tick_tokens=4, prefill_chunk=8)
    bts = be.serve_batch(ep, cp, prompts, budgets)
    for p, m, bt in zip(prompts, budgets, bts):
        rt = ref.serve_reference(ep, cp, p, m)
        assert bt.path == rt.path == "edge"
        assert bt.tokens == rt.tokens and len(bt.tokens) == m
        assert abs(bt.uncertainty - rt.uncertainty) < 1e-5


def test_share_hints_keep_prefix_sharing_under_chunking(edges, cloud,
                                                        monkeypatch):
    """Shared-prefix prompts keep block-level prefix sharing when chunked
    prefill is on: ``share_hints`` routes them down the monolithic admit
    path (a chunked ``begin`` defers index registration until finalize,
    which would forfeit same-wave sharing), while a prompt with a unique
    first block still chunks.  Token parity with ``serve_reference``
    holds throughout."""
    em, ep = edges["dense"]
    cm, cp = cloud
    pref = ((np.arange(16) * 3) % 512).astype(np.int32)     # 2 full blocks
    prompts = [np.concatenate([pref, ((np.arange(5) * 11 + o) % 512)
                               .astype(np.int32)]) for o in range(3)]
    prompts.append(((np.arange(25) * 13 + 200) % 512).astype(np.int32))
    begin_lens = []
    orig_begin = PagedKV.begin
    monkeypatch.setattr(
        PagedKV, "begin",
        lambda self, b, prompt, need: begin_lens.append(
            int(np.asarray(prompt).size)) or orig_begin(
                self, b, prompt, need))
    ref = CollaborativeEngine(em, cm, temperature=0.0,
                              policy=ThresholdPolicy(1.1), use_cache=False)
    be = BatchedEngine(em, cm, batch_size=4, temperature=0.0,
                       policy=ThresholdPolicy(1.1), use_cache=False,
                       tick_tokens=4, kv_layout="paged", kv_block_size=8,
                       prefill_chunk=8)
    bts = be.serve_batch(ep, cp, prompts, 6)
    for p, bt in zip(prompts, bts):
        rt = ref.serve_reference(ep, cp, p, 6)
        assert bt.path == rt.path == "edge"
        assert bt.tokens == rt.tokens
    st = be.stats()
    # first registrant doesn't count as a hit; its two wave twins do
    assert st["kv_prefix_hits"] == 2 and st["kv_shared_blocks"] > 0
    # only the unique-first-block prompt took the chunked begin path
    assert begin_lens == [25]


# ---------------------------------------------------------------- escalation
@pytest.mark.parametrize("esc", ["speculative", "cloud", "skeleton"])
def test_recurrent_escalation_parity(esc, edges, cloud):
    """Every grouped escalation mode matches the reference for a recurrent
    edge — including speculative, whose rewind is the batched replay."""
    em, ep = edges["ssm"]
    cm, cp = cloud
    prompts = _prompts(512, [(8, 0), (6, 3), (10, 5)])
    ref = CollaborativeEngine(em, cm, temperature=0.0,
                              policy=policy_from_legacy(esc, -1.0),
                              use_cache=False, skeleton_len=4)
    be = BatchedEngine(em, cm, batch_size=2, temperature=0.0,
                       policy=policy_from_legacy(esc, -1.0),
                       use_cache=False, skeleton_len=4, tick_tokens=4)
    rts = [ref.serve_reference(ep, cp, p, 8) for p in prompts]
    bts = be.serve_batch(ep, cp, prompts, 8)
    for rt, bt in zip(rts, bts):
        assert bt.path == rt.path == esc
        assert bt.tokens == rt.tokens


@pytest.mark.parametrize("fam", EDGE_ARCHS)
def test_all_family_speculative_parity(fam, edges, cloud):
    """All five families (dense transformer, moe, ssm, hybrid, xlstm) pass
    batched-vs-serve_reference parity through speculative escalation.
    max_new > gamma forces multiple rounds, so partial accepts exercise
    mid-stream rewinds (pos writes for KV, replays for recurrent state)."""
    em, ep = edges[fam]
    cm, cp = cloud
    prompts = _prompts(512, [(8, 0), (6, 3)])
    ref = CollaborativeEngine(em, cm, gamma=3, temperature=0.0,
                              policy=SpeculativePolicy(-1.0), use_cache=False)
    be = BatchedEngine(em, cm, batch_size=2, gamma=3, temperature=0.0,
                       policy=SpeculativePolicy(-1.0), use_cache=False,
                       tick_tokens=4)
    rts = [ref.serve_reference(ep, cp, p, 8) for p in prompts]
    bts = be.serve_batch(ep, cp, prompts, 8)
    for rt, bt in zip(rts, bts):
        assert bt.path == rt.path == "speculative"
        assert bt.tokens == rt.tokens


@pytest.mark.parametrize("fam", RECURRENT)
def test_recurrent_speculation_lossless(fam, edges, cloud):
    """Greedy speculative escalation with a recurrent draft equals cloud-
    only greedy decoding — losslessness survives the batched replay."""
    em, ep = edges[fam]
    cm, cp = cloud
    prompts = _prompts(512, [(8, 0), (6, 3)])
    be = BatchedEngine(em, cm, batch_size=2, temperature=0.0,
                       policy=SpeculativePolicy(-1.0), use_cache=False)
    bts = be.serve_batch(ep, cp, prompts, 8)
    for p, bt in zip(prompts, bts):
        base = autoregressive_baseline(cm, cp, p, 8, temperature=0.0)
        assert bt.tokens == base


def test_recurrent_cloud_side_replay(edges, cloud):
    """A recurrent CLOUD (dense edge drafting for a hybrid verifier) also
    rides the batched path: the target-side rewind is the replay."""
    em, ep = edges["dense"]
    cm, cp = edges["hybrid"]
    prompts = _prompts(512, [(8, 0), (6, 3)])
    ref = CollaborativeEngine(em, cm, temperature=0.0,
                              policy=SpeculativePolicy(-1.0), use_cache=False)
    be = BatchedEngine(em, cm, batch_size=2, temperature=0.0,
                       policy=SpeculativePolicy(-1.0), use_cache=False)
    rts = [ref.serve_reference(ep, cp, p, 6) for p in prompts]
    bts = be.serve_batch(ep, cp, prompts, 6)
    for rt, bt in zip(rts, bts):
        assert bt.tokens == rt.tokens


def test_no_per_request_snapshot_replay(edges, cloud, monkeypatch):
    """The scheduler NEVER falls back to the host-side per-request
    SpecDecoder loop: poisoning it must not affect a recurrent drain."""
    def _boom(*a, **k):
        raise AssertionError("per-request SpecDecoder.generate called "
                             "from the batched scheduler")
    monkeypatch.setattr(spec_mod.SpecDecoder, "generate", _boom)
    em, ep = edges["ssm"]
    cm, cp = cloud
    prompts = _prompts(512, [(8, 0), (6, 3)])
    be = BatchedEngine(em, cm, batch_size=2, temperature=0.0,
                       policy=SpeculativePolicy(-1.0), use_cache=False)
    bts = be.serve_batch(ep, cp, prompts, 6)
    assert all(bt.path == "speculative" and len(bt.tokens) == 6
               for bt in bts)


# ---------------------------------------------------------------- replay op
def test_replay_step_prefix_equivalence(edges):
    """``replay_step(tokens, count)`` lands exactly on the state reached by
    decoding tokens[:count] one by one — for every recurrent family and
    every count, including 0 (frozen slot keeps its state)."""
    for fam in RECURRENT:
        m, params = edges[fam]
        prompt = _prompts(512, [(6, 1)])[0]
        _, cache = m.prefill(params, {"tokens": jnp.asarray(prompt[None, :])},
                             max_seq=16)
        tape = jnp.asarray([[7, 11, 13, 17]], jnp.int32)
        for count in range(tape.shape[1] + 1):
            got = m.replay_step(params, tape, cache,
                                jnp.asarray(count, jnp.int32))
            want = cache
            for t in range(count):
                _, want = m.decode_step(params, tape[:, t:t + 1], want)
            lg_g, _ = m.decode_step(params, jnp.asarray([[23]], jnp.int32),
                                    got)
            lg_w, _ = m.decode_step(params, jnp.asarray([[23]], jnp.int32),
                                    want)
            np.testing.assert_allclose(np.asarray(lg_g), np.asarray(lg_w),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"{fam} count={count}")


# ---------------------------------------------------------------- cow
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_cow_shared_prefix_spec_rewind_parity(layout, edges, cloud):
    """CoW correctness end-to-end: slots sharing a prompt prefix (an exact
    twin included) diverge mid-stream, then take speculative rewinds
    (threshold -1 escalates everyone; max_new > gamma forces multi-round
    partial accepts).  Tokens must match the unshared ``serve_reference``
    byte-for-byte on both layouts — on paged, the escalation group's
    draft AND verify pools shared the prefix blocks and forked them at
    first divergence."""
    em, ep = edges["dense"]
    cm, cp = cloud
    pref = ((np.arange(16) * 3) % 512).astype(np.int32)     # 2 full blocks
    prompts = [np.concatenate([pref,
                               ((np.arange(5) * 11 + o) % 512)
                               .astype(np.int32)]) for o in range(2)]
    prompts.append(prompts[0].copy())           # exact twin: partial tail
    ref = CollaborativeEngine(em, cm, gamma=3, temperature=0.0,
                              policy=SpeculativePolicy(-1.0), use_cache=False)
    be = BatchedEngine(em, cm, batch_size=3, gamma=3, temperature=0.0,
                       policy=SpeculativePolicy(-1.0), use_cache=False,
                       tick_tokens=4, kv_layout=layout, kv_block_size=8)
    rts = [ref.serve_reference(ep, cp, p, 8) for p in prompts]
    bts = be.serve_batch(ep, cp, prompts, 8)
    for rt, bt in zip(rts, bts):
        assert bt.path == rt.path == "speculative"
        assert bt.tokens == rt.tokens


# ---------------------------------------------------------------- paged read
def test_paged_decode_backend_dispatch_parity():
    """The dispatched paged decode read (Pallas kernel / jnp oracle) agrees
    with the full-width block-table gather path it replaces."""
    cfg = get_config("smollm-135m").reduced()
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    from repro.core.paged_cache import (BlockPool, prompt_cache_to_blocks,
                                        write_pool_blocks)
    bs, nb, mb = 8, 9, 4
    cache = m.init_paged_cache(nb, bs, 3, mb)
    pool = BlockPool(nb, bs)
    rng = np.random.default_rng(0)
    tables, poss = [], []
    for b, S in enumerate([9, 6, 12]):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, S)), jnp.int32)
        nblk = pool.blocks_for(S)
        blocks = pool.alloc(b, nblk)
        _, c1 = m.prefill(params, {"tokens": toks}, max_seq=nblk * bs)
        kb, vb = prompt_cache_to_blocks(c1, bs)
        cache["k"], cache["v"] = write_pool_blocks(
            cache["k"], cache["v"], jnp.asarray(blocks, jnp.int32), kb, vb)
        row = np.zeros((mb,), np.int32)
        row[:nblk] = blocks
        tables.append(row)
        poss.append(S)
    cache["table"] = jnp.asarray(np.stack(tables))
    cache["pos"] = jnp.asarray(poss, jnp.int32)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 1)), jnp.int32)

    lg_gather, c_gather = m.paged_decode_step(params, tok, cache,
                                              attn_backend="gather")
    lg_ref, c_ref = m.paged_decode_step(params, tok, cache,
                                        attn_backend="ref")
    lg_kern, _ = m.paged_decode_step(params, tok, cache,
                                     attn_backend="kernel")
    np.testing.assert_allclose(np.asarray(lg_ref), np.asarray(lg_gather),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lg_kern), np.asarray(lg_gather),
                               rtol=1e-4, atol=1e-4)
    # both paths write the same K/V projection, computed in two float32
    # programs whose fusions round differently in the last bits
    for key in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(c_ref[key], np.float32),
            np.asarray(c_gather[key], np.float32), rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c_ref["pos"]),
                                  np.asarray(c_gather["pos"]))
