"""Token-level mixture: speculative decoding (survey §2.4).

Edge SLM drafts gamma tokens; cloud LLM verifies them in ONE parallel pass
(modified rejection sampling, Leviathan et al. / survey §2.4.1).  The scheme
is *lossless*: the output distribution equals sampling from the target model
alone — `speculative_sample` is the pure, property-tested core.

Cache bookkeeping (the part the survey leaves implicit, and where the
architecture families differ):

* KV-cache models (dense/moe/vlm/encdec) roll back rejected tokens by
  resetting ``pos`` — stale entries are masked out and later overwritten.
* Recurrent-state models (ssm/xlstm/hybrid) cannot rewind; the reference
  ``SpecDecoder`` snapshots the state before each round and REPLAYS the
  accepted prefix (one extra extend pass — this cost shows up in
  SpecStats.replay_passes and in the benchmarks).  ``BatchedSpecDecoder``
  replays on device instead: each slot re-advances through its own accepted
  prefix via the model's batched ``replay_step`` (``core/seq_state.py``).

Invariant maintained by ``SpecDecoder.generate``: both caches contain
``sequence[:-1]``; ``sequence[-1]`` ("last token") is pending.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.analysis import hot_path


def _probs(logits, temperature: float):
    """softmax(l/T) with T=0 -> one-hot argmax (greedy)."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[-1],
                              dtype=jnp.float32)
    return jax.nn.softmax(logits / temperature, axis=-1)


@functools.partial(jax.jit, static_argnames=("temperature",))
def speculative_sample(rng, target_logits, draft_logits, draft_tokens,
                       temperature: float = 1.0):
    """Modified rejection sampling over a gamma-token draft.

    target_logits: (gamma+1, V) — logits for draft positions 0..gamma-1 plus
        the bonus position after a fully-accepted draft.
    draft_logits: (gamma, V); draft_tokens: (gamma,) int32.
    Returns (n_accepted (), next_token ()): the emitted tokens are
    draft_tokens[:n_accepted] + [next_token].
    """
    gamma = draft_tokens.shape[0]
    p = _probs(target_logits, temperature)            # (gamma+1, V)
    q = _probs(draft_logits, temperature)             # (gamma, V)
    r_accept, r_resample = jax.random.split(rng)

    p_tok = jnp.take_along_axis(p[:gamma], draft_tokens[:, None], axis=1)[:, 0]
    q_tok = jnp.take_along_axis(q, draft_tokens[:, None], axis=1)[:, 0]
    ratio = p_tok / jnp.maximum(q_tok, 1e-20)
    u = jax.random.uniform(r_accept, (gamma,))
    accept = u < jnp.minimum(ratio, 1.0)
    n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))

    # residual distribution at the first rejected position (or bonus at gamma)
    q_pad = jnp.concatenate([q, jnp.zeros((1, q.shape[1]), q.dtype)], axis=0)
    resid = jnp.clip(p[n_acc] - q_pad[n_acc], 0.0, None)
    resid_sum = jnp.sum(resid)
    resid = jnp.where(resid_sum > 0, resid / jnp.maximum(resid_sum, 1e-20),
                      p[n_acc])
    next_token = jax.random.categorical(r_resample, jnp.log(resid + 1e-20))
    return n_acc, next_token.astype(jnp.int32)


def acceptance_rate_bound(p, q):
    """Theoretical per-token acceptance prob: 1 - TV(p, q) = sum min(p, q).
    Used by tests and by the gamma controller."""
    return jnp.sum(jnp.minimum(p, q), axis=-1)


@dataclasses.dataclass
class SpecStats:
    draft_calls: int = 0
    target_passes: int = 0
    replay_passes: int = 0
    rounds: int = 0
    accepted: List[int] = dataclasses.field(default_factory=list)
    tokens_out: int = 0

    @property
    def mean_accepted(self) -> float:
        return float(np.mean(self.accepted)) if self.accepted else 0.0

    @property
    def tokens_per_target_pass(self) -> float:
        tp = self.target_passes + self.replay_passes
        return self.tokens_out / tp if tp else 0.0

    def summary(self) -> dict:
        return {
            "rounds": self.rounds,
            "draft_calls": self.draft_calls,
            "target_passes": self.target_passes,
            "replay_passes": self.replay_passes,
            "mean_accepted": self.mean_accepted,
            "tokens_out": self.tokens_out,
            "tokens_per_target_pass": self.tokens_per_target_pass,
        }


class AdaptiveGamma:
    """PEARL/DISCO-style draft-length control: lengthen the draft when
    acceptance is high, shorten when the target keeps rejecting."""

    def __init__(self, gamma: int = 4, lo: int = 1, hi: int = 16,
                 up: float = 0.85, down: float = 0.4):
        self.gamma, self.lo, self.hi, self.up, self.down = gamma, lo, hi, up, down

    def update(self, n_acc: int, gamma_used: int) -> int:
        rate = n_acc / max(gamma_used, 1)
        if rate >= self.up:
            self.gamma = min(self.gamma + 1, self.hi)
        elif rate <= self.down:
            self.gamma = max(self.gamma - 1, self.lo)
        return self.gamma


class SpecDecoder:
    """Edge-draft / cloud-verify decoding loop (B=1 sequences).

    draft_model / target_model: repro Model objects sharing a vocabulary.
    """

    def __init__(self, draft_model, target_model, *, gamma: int = 4,
                 temperature: float = 1.0, adaptive: bool = False):
        self.draft = draft_model
        self.target = target_model
        self.gamma = gamma
        self.temperature = temperature
        self.adaptive = AdaptiveGamma(gamma) if adaptive else None
        self._draft_step = jax.jit(
            lambda p, t, c: draft_model.decode_step(p, t, c))
        self._target_extend = jax.jit(
            lambda p, t, c: target_model.extend_step(p, t, c))
        self._draft_extend = jax.jit(
            lambda p, t, c: draft_model.extend_step(p, t, c))

    # ----------------------------------------------------------------
    def _snapshot(self, model, cache):
        if model.rewindable_cache:
            return cache["pos"]
        return jax.tree.map(lambda x: x, cache)     # shallow copy of pytree

    def _restore_and_replay(self, model, params, cache, snap, tokens):
        """Bring `model`'s cache to contain ...prefix + tokens."""
        if model.rewindable_cache:
            cache = model.rewind(cache, snap)
            if tokens.size:
                _, cache = (self._target_extend if model is self.target
                            else self._draft_extend)(params, tokens[None, :], cache)
            return cache, (1 if tokens.size else 0)
        # recurrent: replay from snapshot
        if tokens.size:
            _, cache = (self._target_extend if model is self.target
                        else self._draft_extend)(params, tokens[None, :], snap)
            return cache, 1
        return snap, 0

    # ----------------------------------------------------------------
    def generate(self, draft_params, target_params, prompt, max_new: int,
                 rng=None):
        """prompt: (S,) or (1,S) int32. Returns (tokens list, SpecStats)."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        prompt = jnp.atleast_2d(jnp.asarray(prompt, jnp.int32))
        assert prompt.shape[0] == 1, "SpecDecoder operates on B=1 sequences"
        S = prompt.shape[1]
        max_seq = S + max_new + 2 * max(self.gamma, 16) + 8

        d_lg, d_cache = self.draft.prefill(
            draft_params, {"tokens": prompt[:, :-1]}, max_seq=max_seq)
        t_lg, t_cache = self.target.prefill(
            target_params, {"tokens": prompt[:, :-1]}, max_seq=max_seq)

        stats = SpecStats()
        out: List[int] = []
        last = prompt[:, -1:]                          # pending token (1,1)

        while len(out) < max_new:
            gamma = self.adaptive.gamma if self.adaptive else self.gamma
            rng, r_draft, r_ver = jax.random.split(rng, 3)

            d_snap = self._snapshot(self.draft, d_cache)
            t_snap = self._snapshot(self.target, t_cache)

            # ---- draft gamma tokens (+1 call to keep the cache aligned)
            draft_tokens, draft_logits = [], []
            tok = last
            for i in range(gamma):
                lg, d_cache = self._draft_step(draft_params, tok, d_cache)
                stats.draft_calls += 1
                if self.temperature == 0.0:
                    nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                else:
                    r_draft, rr = jax.random.split(r_draft)
                    nxt = jax.random.categorical(
                        rr, lg / self.temperature, axis=-1).astype(jnp.int32)
                draft_logits.append(lg[0])
                draft_tokens.append(int(nxt[0]))
                tok = nxt[:, None]
            _, d_cache = self._draft_step(draft_params, tok, d_cache)
            stats.draft_calls += 1

            # ---- verify in one target pass over [last, d_0..d_{gamma-1}]
            ver_in = jnp.concatenate(
                [last, jnp.asarray(draft_tokens, jnp.int32)[None, :]], axis=1)
            t_logits, t_cache = self._target_extend(target_params, ver_in, t_cache)
            stats.target_passes += 1

            n_acc, next_tok = speculative_sample(
                r_ver, t_logits[0], jnp.stack(draft_logits),
                jnp.asarray(draft_tokens, jnp.int32),
                temperature=self.temperature)
            n_acc, next_tok = int(n_acc), int(next_tok)

            # ---- commit & resync
            emitted = draft_tokens[:n_acc] + [next_tok]
            out.extend(emitted)
            stats.rounds += 1
            stats.accepted.append(n_acc)
            if self.adaptive:
                self.adaptive.update(n_acc, gamma)

            acc_tokens = jnp.asarray([int(last[0, 0])] + draft_tokens[:n_acc],
                                     jnp.int32)
            if self.target.rewindable_cache:
                t_cache = self.target.rewind(t_cache, int(t_snap) + n_acc + 1)
            else:
                _, t_cache = self._target_extend(
                    target_params, acc_tokens[None, :], t_snap)
                stats.replay_passes += 1
            if self.draft.rewindable_cache:
                d_cache = self.draft.rewind(d_cache, int(d_snap) + n_acc + 1)
            else:
                _, d_cache = self._draft_extend(
                    draft_params, acc_tokens[None, :], d_snap)
                stats.replay_passes += 1
            last = jnp.asarray([[next_tok]], jnp.int32)

        stats.tokens_out = len(out)
        return out[:max_new], stats


class BatchedSpecDecoder:
    """Grouped edge-draft / cloud-verify decoding for the serving scheduler.

    Where ``SpecDecoder`` runs one request with a host round-trip per draft
    token, this operates on a padded GROUP of requests with stacked per-slot
    caches (leading slot axis, per-slot scalar ``pos``):

      * drafting is ONE jitted ``lax.scan`` of gamma+1 steps over the whole
        group;
      * verification is ONE batched target extend over all slots;
      * acceptance (vmapped ``speculative_sample``) and the per-slot cache
        rewind both happen on device — one host sync per ROUND, per group.

    Cache handling is family-agnostic: each model's step/extend/rewind go
    through ``core.seq_state.SpecOps``, so any edge/cloud family pair —
    mixed ones included — shares the same rounds.  KV caches (dense or
    paged) rewind with a ``pos`` write; recurrent-state families
    (ssm/xlstm/hybrid) rewind by replaying each slot's accepted prefix
    from the pre-round state via the model's batched ``replay_step``
    (padded draft tape + per-slot ``jnp.where`` state select) — no
    per-request snapshot+replay anywhere.

    The caller owns admission: ``generate_group`` takes already-prefilled
    stacked caches (see ``core.seq_state.stack_slot_caches`` /
    ``write_slot``) so the scheduler can reuse its slot machinery.

    ``kv_layout="paged"`` runs the same rounds over paged caches (shared
    block pool + per-slot block tables, ``core/paged_cache.py``): drafting
    and verification go through the models' batched ``paged_decode_step`` /
    ``paged_extend_step``, and the per-slot rewind is STILL just the
    ``pos`` write — rejected draft entries stay in their allocated blocks,
    masked out and overwritten by the next round.  The caller must have
    grown each slot's block table to cover prompt + budget + one round of
    draft overdraft before calling ``generate_group``.

    ``mode`` picks the speculation lane:

    * ``"linear"`` (default) — the gamma-token chain above, any family pair.
    * ``"tree"`` — each slot drafts a PACKED TOKEN TREE (static
      ``TreePlan`` topology, pow2-padded width) level-by-level via top-k
      expansion, each level a rectangular-masked extend over ONLY its new
      nodes (each node forwarded exactly once per round); verification is
      ONE batched tree-masked target extend (``SpecOps.extend_tree`` — the
      Pallas tree-attention kernel on TPU) and acceptance walks the
      longest target-consistent root path (``tree_accept``).  The accepted
      path's K/V sit at non-contiguous but position-correct tree rows, so
      BOTH commits are row gathers down to the contiguous prefix
      (``SpecOps.commit_permute`` — no replay forward pass).  Dense-layout
      attention families only (``tree_supported``); group states are
      always dense.
    * ``"self"`` — no second model: the draft model's OWN early-exit head
      (first ``exit_layer`` blocks + shared LM head,
      ``self_speculative.partial_extend_step``) drafts into the shared
      cache and the full depth verifies, overwriting the shallow K/V.
      One cache, one params pytree (``second_model_params == 0``); use
      ``generate_group_self``.

    ``counters`` accumulates per-lane totals across ``generate_group``
    calls: member_rounds (active member-rounds = verify passes),
    draft_tokens (candidate tokens drafted), verify_tokens (positions the
    target forward covers, replay included), accepted_tokens and
    emitted_tokens — the engine's ``stats()`` derives
    ``spec_accept_rate`` / ``accepted_tokens_per_step`` from these.
    """

    def __init__(self, draft_model, target_model, *, gamma: int = 4,
                 temperature: float = 0.0, kv_layout: str = "dense",
                 mode: str = "linear", branching=None, exit_layer=None):
        from repro.core.seq_state import SpecOps, layout_for
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if mode not in ("linear", "tree", "self"):
            raise ValueError(f"unknown speculation mode {mode!r}; "
                             "known: linear | tree | self")
        self.gamma = gamma
        self.temperature = temperature
        self.kv_layout = kv_layout
        self.mode = mode
        self.counters = {"member_rounds": 0, "draft_tokens": 0,
                         "verify_tokens": 0, "accepted_tokens": 0,
                         "emitted_tokens": 0}
        if mode == "linear":
            self._dops = SpecOps(draft_model, layout_for(draft_model, kv_layout))
            self._tops = SpecOps(target_model, layout_for(target_model, kv_layout))
            self._round = jax.jit(self._round_impl)
            self._per_round = (gamma, gamma + 1)
        elif mode == "tree":
            from repro.core.tree_speculation import TreePlan, branching_for
            if not self.tree_supported(draft_model, target_model):
                raise ValueError(
                    "tree speculation needs dense-layout attention families "
                    f"on both models, got {draft_model.cfg.family!r} / "
                    f"{target_model.cfg.family!r} (DESIGN.md "
                    "§Arch-applicability)")
            # tree groups always run dense per-slot caches: block masks are
            # a dense-layout feature (paged extends stay linear-order)
            self._dops = SpecOps(draft_model, "dense")
            self._tops = SpecOps(target_model, "dense")
            self.plan = TreePlan(branching if branching is not None
                                 else branching_for(2, gamma))
            self._round = jax.jit(self._tree_round_impl)
            self._per_round = (self.plan.n - 1, self.plan.n_pad)
        else:                                            # self
            from repro.core.self_speculative import partial_extend_step
            model = draft_model
            if not self.self_supported(model):
                raise ValueError(
                    "self-speculation needs a scan-stacked attention edge "
                    f"model, got family {model.cfg.family!r}")
            k = exit_layer if exit_layer is not None \
                else max(model.cfg.num_layers // 2, 1)
            if not 0 < k < model.cfg.num_layers:
                raise ValueError(f"exit_layer {k} out of range "
                                 f"(0, {model.cfg.num_layers})")
            self.exit_layer = k
            self.second_model_params = 0
            cfg = model.cfg
            self._tops = SpecOps(model, "dense")
            self._vpartial = jax.vmap(
                lambda p, t, c: partial_extend_step(p, t, c, cfg, k),
                in_axes=(None, 0, 0))
            self._round = jax.jit(self._self_round_impl)
            self._per_round = (gamma, gamma + 1)
        # every round rewinds the states it drafted and verified on
        self.rewindable = self._tops.rewindable and (
            mode == "self" or self._dops.rewindable)

    @staticmethod
    def tree_supported(draft_model, target_model) -> bool:
        fams = ("dense", "moe", "vlm")
        return (draft_model.cfg.family in fams
                and target_model.cfg.family in fams)

    @staticmethod
    def self_supported(model) -> bool:
        return model.cfg.family in ("dense", "moe", "vlm")

    def _round_impl(self, draft_params, target_params, d_slots, t_slots,
                    last, active, rng):
        """One draft/verify/commit round over the whole group.

        last: (G, 1, 1) pending tokens; active: (G,) bool — frozen slots
        keep their cache position and pending token.  Both caches satisfy
        the SpecDecoder invariant (contain sequence[:-1]) on entry and exit.
        """
        gamma = self.gamma
        G = last.shape[0]
        d_snap = self._dops.snapshot(d_slots)
        t_snap = self._tops.snapshot(t_slots)
        r_draft, r_ver = jax.random.split(rng)

        # ---- draft gamma tokens (+1 step so a fully-accepted draft's last
        # token is already in the cache when we commit gamma+1 tokens)
        def body(carry, r):
            caches, tok = carry
            lg, caches = self._dops.step(draft_params, tok, caches)  # (G, V)
            if self.temperature == 0.0:
                nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(
                    r, lg / self.temperature, axis=-1).astype(jnp.int32)
            return (caches, nxt[:, None, None]), (nxt, lg)

        (d_slots, _), (toks, lgs) = jax.lax.scan(
            body, (d_slots, last), jax.random.split(r_draft, gamma + 1))
        draft_toks = toks[:gamma].T                  # (G, gamma)
        draft_lgs = jnp.moveaxis(lgs[:gamma], 0, 1)  # (G, gamma, V)

        # ---- verify in one batched target pass over [last, d_0..d_{g-1}].
        # On a mesh this is THE wave crossing: the edge's data-sharded
        # draft tape is all-gathered over the data axes in one collective
        # per round, the tensor-parallel cloud verifies the replicated
        # wave, and the committed result is constrained back to per-slot
        # data sharding below (scatter_wave).  Identity off-mesh.
        ver_in = jnp.concatenate([last[:, :, 0], draft_toks], axis=1)  # (G,g+1)
        ver_in, draft_toks = runtime.gather_wave(ver_in, draft_toks)
        t_logits, t_slots = self._tops.extend(target_params, ver_in, t_slots)

        n_acc, next_tok = jax.vmap(
            functools.partial(speculative_sample,
                              temperature=self.temperature)
        )(jax.random.split(r_ver, G), t_logits, draft_lgs, draft_toks)

        # ---- per-slot rewind: caches now hold sequence + the full draft;
        # commit each slot's accepted prefix [last, d_0..d_{n_acc-1}]
        # (counts = 0 freezes inactive slots on their snapshot).
        counts = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
        d_slots = self._dops.commit(draft_params, d_slots, d_snap,
                                    ver_in, counts)
        t_slots = self._tops.commit(target_params, t_slots, t_snap,
                                    ver_in, counts)
        last = runtime.scatter_wave(
            jnp.where(active[:, None, None], next_tok[:, None, None], last))
        return d_slots, t_slots, last, draft_toks, n_acc, next_tok

    def _tree_round_impl(self, draft_params, target_params, d_slots, t_slots,
                         last, active, rng):
        """One packed-tree draft/verify/commit round over the whole group.

        Drafting expands the static ``TreePlan`` level-by-level and
        INCREMENTALLY: each span (root, then each level) is one rectangular
        tree-masked extend over only that span's NEW nodes — the mask's
        earlier columns cover the tree rows previous spans already wrote to
        the cache — so a round forwards each of the ``n`` nodes exactly
        once (O(n), not the O(n^2) recompute-from-snapshot alternative).
        Parent-row logits feed static top-k child selection.  Verification
        is one batched tree-masked target extend over all ``n_pad`` nodes —
        the same gather/scatter wave crossing as the linear round — and
        ``tree_accept`` walks the longest target-consistent root path per
        slot.  Accepted-path K/V sit at non-contiguous tree positions, so a
        bare ``pos`` write would keep sibling garbage inside the visible
        prefix — but every node's row is position-correct (written once at
        RoPE position snap + depth), so BOTH commits are row permutes
        (``commit_permute``): gather the accepted path down to the
        contiguous prefix, zero extra forward passes.
        """
        from repro.core.tree_speculation import tree_accept
        plan = self.plan
        G = last.shape[0]
        D = plan.depth
        mask = jnp.asarray(plan.mask)
        depths = jnp.asarray(plan.depths)
        d_snap = self._dops.snapshot(d_slots)
        t_snap = self._tops.snapshot(t_slots)

        # ---- draft: deterministic top-k tree expansion (OPT-Tree style);
        # node c's acceptance distribution q is its PARENT's draft logits
        toks = jnp.zeros((G, plan.n_pad), jnp.int32).at[:, 0].set(last[:, 0, 0])
        q_lgs = [None] * plan.n_pad
        spans = [(0, 1)] + list(plan.levels)     # contiguous: b_i == a_{i+1}
        for si, (a, b) in enumerate(spans):
            # extend ONLY nodes [a, b); mask rows a..b over all b tree
            # columns written so far; RoPE offset depths - a because the
            # cache pos already advanced to snap + a
            lgs, d_slots = self._dops.extend_tree(
                draft_params, toks[:, a:b], d_slots,
                mask[a:b, :b], depths[a:b] - a)
            if si + 1 == len(spans):
                break                            # deepest level: K/V only
            lo, hi = spans[si + 1]
            by_parent = {}
            for c in range(lo, hi):
                by_parent.setdefault(int(plan.parent[c]), []).append(c)
            for pnode, kids in sorted(by_parent.items()):
                plg = lgs[:, pnode - a]                      # (G, V)
                top = jax.lax.top_k(plg, len(kids))[1]
                for j, c in enumerate(kids):
                    toks = toks.at[:, c].set(top[:, j].astype(jnp.int32))
                    q_lgs[c] = plg
        V = q_lgs[plan.levels[0][0]].shape[-1]
        zero = jnp.zeros((G, V), jnp.float32)
        q_logits = jnp.stack([zero if l is None else l.astype(jnp.float32)
                              for l in q_lgs], axis=1)       # (G, n_pad, V)

        # ---- verify: ONE batched tree-masked target extend over the
        # flattened trees.  Same wave crossing as the linear round: the
        # data-sharded trees are all-gathered for the tensor-parallel
        # verifier, the committed result scattered back below.
        toks = runtime.gather_wave(toks)
        t_lgs, t_slots = self._tops.extend_tree(target_params, toks, t_slots,
                                                mask, depths)

        n_acc, em, path = jax.vmap(
            functools.partial(tree_accept, plan=plan,
                              temperature=self.temperature)
        )(jax.random.split(rng, G), t_lgs, q_logits, toks)
        next_tok = jnp.take_along_axis(em, n_acc[:, None], axis=1)[:, 0]

        # ---- commit the accepted root path.  Both caches hold every tree
        # node's K/V at row snap + node with RoPE position snap + depth
        # (the draft wrote them level by level, the verify in one pass), so
        # both commits are row PERMUTES — gather the accepted path down to
        # the contiguous prefix — with zero extra forward passes.
        counts = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
        d_slots = self._dops.commit_permute(d_slots, d_snap, path, counts)
        t_slots = self._tops.commit_permute(t_slots, t_snap, path, counts)
        last = runtime.scatter_wave(
            jnp.where(active[:, None, None], next_tok[:, None, None], last))
        return d_slots, t_slots, last, em[:, :D], n_acc, next_tok

    def _self_round_impl(self, params, slots, last, active, rng):
        """One self-speculative round: the model's first ``exit_layer``
        blocks + shared head draft a gamma-chain into the SHARED cache
        (shallow K/V at the draft positions, ``pos`` advanced manually),
        then the full depth verifies from the snapshot — overwriting every
        layer's K/V at those positions — and the commit is the usual
        ``pos`` write.  One cache, one params pytree."""
        gamma = self.gamma
        G = last.shape[0]
        snap = self._tops.snapshot(slots)
        r_draft, r_ver = jax.random.split(rng)

        def body(carry, r):
            caches, tok = carry
            lg, caches = self._vpartial(params, tok, caches)  # tok (G,1,1)
            lg = lg[:, 0, 0]                                 # (G, V)
            caches = {**caches, "pos": caches["pos"] + 1}
            if self.temperature == 0.0:
                nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(
                    r, lg / self.temperature, axis=-1).astype(jnp.int32)
            return (caches, nxt[:, None, None]), (nxt, lg)

        (slots, _), (toks, lgs) = jax.lax.scan(
            body, (slots, last), jax.random.split(r_draft, gamma))
        draft_toks = toks.T                                  # (G, gamma)
        draft_lgs = jnp.moveaxis(lgs, 0, 1)                  # (G, gamma, V)

        ver_in = jnp.concatenate([last[:, :, 0], draft_toks], axis=1)
        ver_in, draft_toks = runtime.gather_wave(ver_in, draft_toks)
        slots = self._tops.reset(slots, snap)
        t_logits, slots = self._tops.extend(params, ver_in, slots)

        n_acc, next_tok = jax.vmap(
            functools.partial(speculative_sample,
                              temperature=self.temperature)
        )(jax.random.split(r_ver, G), t_logits, draft_lgs, draft_toks)

        counts = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
        slots = self._tops.commit(params, slots, snap, ver_in, counts)
        last = runtime.scatter_wave(
            jnp.where(active[:, None, None], next_tok[:, None, None], last))
        return slots, last, draft_toks, n_acc, next_tok

    @hot_path
    def generate_group(self, draft_params, target_params, d_slots, t_slots,
                       last, max_news, rng=None):
        """Decode a prefilled group until every member has its tokens.

        max_news: per-slot budget (0 for padding slots).  Returns
        (token lists, per-member stats dicts with rounds/accepted).
        ``mode="linear"`` and ``mode="tree"`` share this loop — a tree
        round's tape is its emitted-path tokens, so the per-round emission
        is ``tape[i, :n_acc] + [next_tok]`` in both.
        """
        assert self.mode in ("linear", "tree"), \
            "self mode decodes one shared state: use generate_group_self"
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        G = last.shape[0]
        remaining = np.array(max_news, np.int64)    # host list, not a sync
        out: List[List[int]] = [[] for _ in range(G)]
        member_stats = [{"rounds": 0, "accepted": []} for _ in range(G)]

        while (remaining > 0).any():
            active = jnp.asarray(remaining > 0)
            rng, r = jax.random.split(rng)
            d_slots, t_slots, last, draft_toks, n_acc, next_tok = self._round(
                draft_params, target_params, d_slots, t_slots, last, active, r)
            self._collect(remaining, draft_toks, n_acc, next_tok, out,
                          member_stats)
        return out, member_stats

    @hot_path
    def generate_group_self(self, params, slots, last, max_news, rng=None):
        """Self-speculative twin of ``generate_group``: ONE model, ONE
        stacked dense cache (shallow draft + full-depth verify share it)."""
        assert self.mode == "self"
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        G = last.shape[0]
        remaining = np.array(max_news, np.int64)    # host list, not a sync
        out: List[List[int]] = [[] for _ in range(G)]
        member_stats = [{"rounds": 0, "accepted": []} for _ in range(G)]

        while (remaining > 0).any():
            active = jnp.asarray(remaining > 0)
            rng, r = jax.random.split(rng)
            slots, last, draft_toks, n_acc, next_tok = self._round(
                params, slots, last, active, r)
            self._collect(remaining, draft_toks, n_acc, next_tok, out,
                          member_stats)
        return out, member_stats

    @hot_path
    def _collect(self, remaining, draft_toks, n_acc, next_tok, out,
                 member_stats):
        """Host half of a round: slice each active member's emission off
        the padded tape and accumulate the lane counters — fed by ONE
        batched pull of the round's device outputs (rule R1)."""
        dt, na, nt = jax.device_get((draft_toks, n_acc, next_tok))  # repro-lint: ok(R1, the single batched per-round device pull)
        per_draft, per_verify = self._per_round
        for i in range(len(out)):
            if remaining[i] <= 0:
                continue
            emitted = [int(t) for t in dt[i, :int(na[i])]] + [int(nt[i])]
            take = min(len(emitted), int(remaining[i]))
            out[i].extend(emitted[:take])
            remaining[i] -= take
            member_stats[i]["rounds"] += 1
            member_stats[i]["accepted"].append(int(na[i]))
            c = self.counters
            c["member_rounds"] += 1
            c["draft_tokens"] += per_draft
            c["verify_tokens"] += per_verify
            c["accepted_tokens"] += int(na[i])
            c["emitted_tokens"] += take


def autoregressive_baseline(model, params, prompt, max_new: int, rng=None,
                            temperature: float = 1.0):
    """Plain target-only decoding — the survey's cloud-only baseline."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    prompt = jnp.atleast_2d(jnp.asarray(prompt, jnp.int32))
    max_seq = prompt.shape[1] + max_new + 4
    step = jax.jit(lambda p, t, c: model.decode_step(p, t, c))
    _, cache = model.prefill(params, {"tokens": prompt[:, :-1]}, max_seq=max_seq)
    tok = prompt[:, -1:]
    out = []
    for _ in range(max_new):
        lg, cache = step(params, tok, cache)
        if temperature == 0.0:
            nxt = jnp.argmax(lg, -1).astype(jnp.int32)
        else:
            rng, rr = jax.random.split(rng)
            nxt = jax.random.categorical(rr, lg / temperature, -1).astype(jnp.int32)
        out.append(int(nxt[0]))
        tok = nxt[:, None]
    return out
