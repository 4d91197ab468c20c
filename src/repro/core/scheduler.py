"""Batched continuous-batching serving scheduler (survey §2.3 at throughput).

The original ``CollaborativeEngine`` serves one request at a time with a
host round-trip per decoded token — fine for tracing the taxonomy, hopeless
for the ROADMAP's "heavy traffic" north star.  ``BatchedEngine`` keeps the
same per-request semantics (cache -> edge -> escalation, identical greedy
tokens) but executes them slot-based and batched:

  * SLOTS — ``batch_size`` slots, each holding one in-flight request.  All
    per-slot device state is a stacked pytree with a leading slot axis and
    a per-slot scalar ``pos``.
  * SEQUENCE STATE — every per-family cache layout lives behind the
    ``SequenceState`` adapter protocol in ``core/seq_state.py``: dense KV
    slabs (the parity oracle), the paged block pool + per-slot block tables
    (``kv_layout="paged"``, the default where both families allow), and
    fixed-size recurrent state (ssm / xlstm / hybrid).  The scheduler calls
    ``admit / flush / prepare_tick / retire`` and reads ``peak_bytes``; it
    never branches on layout or family itself.
  * PREFILL on admission is LENGTH-BUCKETED and, past a threshold,
    CHUNKED.  Short prompts prefill in one shot, their token count padded
    to a pow2 bucket (bit-exact on KV layouts — masked scores are exactly
    zero — and jit-cached per bucket, not per distinct length); prompts
    longer than ``prefill_chunk`` entries (default: ``tick_tokens``)
    prefill ONE chunk per tick into a detached cache
    (``Lane.start_prefill`` / ``advance_prefill``) interleaved with the
    batch's decode ticks, then land through ``SequenceState.finalize`` —
    a long prompt no longer stalls every in-flight slot behind a
    monolithic prefill.  Either way the finished cache reaches the slot
    in one batched scatter per wave.
  * OPEN-LOOP TRAFFIC + LATENCY (``core/traffic.py``): ``submit(at=...)``
    gives every request an arrival time; admission only considers arrived
    requests, and the engine's clock (virtual by default — deterministic
    modeled ms; ``WallClock`` for real time) advances with decode steps
    and prefill chunks.  Per-request lifecycle events (submit / admit /
    first-token / retire, swap + defer counts) are stamped tick-granular
    and rolled into p50/p99 TTFT/TPOT, SLO attainment and
    goodput-under-SLO in ``stats()``.
  * DECODE — one jitted ``lax.scan`` of up to ``tick_tokens`` steps over
    the whole batch, with per-slot uncertainty accumulated ON DEVICE
    (``uncertainty.get_batched_estimator``).  One host sync per tick, not
    per token.  Slots that run out of budget mid-tick keep decoding
    garbage behind an ``active`` mask; their emissions are dropped, and on
    the paged layout those masked writes land in the reserved TRAP block
    so freed blocks can be re-allocated immediately.
  * POLICY — every collaboration decision flows through the ``CollabPolicy``
    hooks (``core/policy.py``); the scheduler contains no escalation-mode
    branching of its own.  ``policy.assign(features)`` runs at admission
    (task assignment: an ``"edge"``-assigned request force-accepts its edge
    output, a ``"cloud"``-assigned one skips the edge entirely and is served
    by a grouped batched cloud generation, ``"collab"`` takes the edge-first
    path below); ``policy.decide(unc, steps, budget)`` runs once per
    retirement wave, vectorized, naming each retiring request's action
    (accept / cloud / skeleton / speculative — one wave can mix them);
    ``policy.feedback(action, quality, cost, features)`` fires per
    completion with the realized quality proxy and cloud-token cost,
    closing the loop for bandit/budget policies.
  * RETIRE / ADMIT each tick: finished slots are grouped by their decided
    action and freed; queued requests are admitted into the freed slots.  Identical prompts admitted in the same
    tick (or while a matching request is still in flight) are COALESCED:
    one leader decodes, the rest are served from its result through the
    semantic cache — restoring the sequential engine's behavior.  On the
    paged layout, admission also consults the ``PagedKV`` prefix-block
    index: requests sharing a block-aligned prompt prefix (twins included,
    whatever tick they arrive in) map the shared blocks physically, with
    copy-on-write at first divergence.
  * PREEMPTION-BY-SWAP (paged layout): when the block pool cannot back a
    waiting request, the scheduler swaps out a victim slot — its KV blocks
    staged to a host buffer (``PagedKV.swap_out``) — admits the waiter,
    and resumes the victim later (``swap_in``, bit-identical content, so
    resumed decodes emit the same tokens).  ANTI-STARVATION POLICY:
    admission stays strict-arrival-order (swapped victims, which predate
    everything queued, resume before new admissions); the victim is the
    occupied slot with the MOST remaining decode steps (tie: youngest
    rid), i.e. the one that would hold its reservation longest; slots
    admitted or resumed in the current wave are never victims (no
    same-tick thrash), nor are slots whose swap-in restore could not fit
    the pool; and a request too large for even an empty pool (its live
    shareable prefix counted) fails fast instead of preempting the whole
    batch.  Every preemption
    admits the head waiter, the queue is finite per drain, and a swapped
    victim re-enters at the head of admission order — so no request can
    starve and no permanent deferral exists (the old defer-forever path is
    gone).
  * ESCALATION runs GROUPED: all slots retired into the same action in a
    tick share one batched cloud decode ("cloud"), one batched skeleton +
    batched edge completion ("skeleton"), or one ``BatchedSpecDecoder``
    group ("speculative").  Groups are padded to ``batch_size`` so every jitted
    shape is compiled once.  Speculative rewind is a ``pos`` write on KV
    layouts and a batched accepted-prefix replay (``Model.replay_step``) on
    recurrent layouts — EVERY family pair, mixed ones included (e.g. mamba2
    draft -> granite verify), runs the same grouped batched escalation.

Serving invariants here are pinned mechanically by ``repro-lint``
(``scripts/repro_lint.py``): the tick loop and escalation groups are
``@hot_path`` — ONE batched ``jax.device_get`` per tick/wave is the
only host readback (rule R1, enforced at runtime by the transfer-guard
tier-1 test); steady-state ticks never retrace (rule R2 + the
``compile_stability`` bench arm); and the scheduler knows nothing about
concrete KV layouts or model families — zero ``isinstance``/attribute
probes against them (rule R4: layout queries go through the
``SequenceState`` protocol, e.g. ``owned_blocks``, and layout dispatch
through ``Lane``, e.g. ``dense_side``).

Remaining gaps (see ROADMAP "Serving architecture"): scheduling is
single-host/single-device.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hot_path
from repro.core.cache import SemanticCache, embed_tokens_mean
from repro.core.policy import (ACTIONS, LANES, cloud_tokens, resolve_policy,
                               trace_quality)
from repro.core.seq_state import (Lane, layout_for,  # noqa: F401 (re-export)
                                  pow2_steps, resolve_kv_layout,
                                  stack_slot_caches, write_slot, write_slots)
from repro.core.speculative import BatchedSpecDecoder
from repro.core.traffic import VirtualClock, latency_rollup


@dataclasses.dataclass
class RequestTrace:
    path: str                       # cache | edge | speculative | cloud | skeleton
    edge_calls: int = 0
    cloud_passes: int = 0
    uncertainty: float = 0.0
    tokens: Optional[List[int]] = None
    # cloud top-k teacher logits for the emitted tokens, when the wave's
    # cloud pass already paid for them: (values, indices) arrays of shape
    # (len(tokens), k) — serve-time distillation supervision
    teacher_topk: Optional[Tuple[np.ndarray, np.ndarray]] = None


# ---------------------------------------------------------------- requests
@dataclasses.dataclass
class _Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    key: Optional[np.ndarray] = None    # semantic-cache key (set at admit)
    lane: Optional[str] = None          # policy.assign outcome (once per req)
    at: Optional[float] = None          # arrival time, clock ms (None = now)
    spent: int = 0                      # edge decode steps actually consumed
    domain: Optional[int] = None        # workload tag for adaptation slicing
    draft: Optional[List[int]] = None   # discarded edge draft (escalations)


@dataclasses.dataclass
class _Slot:
    req: Optional[_Request] = None
    tokens: List[int] = dataclasses.field(default_factory=list)


class BatchedEngine:
    """Slot-based collaborative serving engine (see module docstring).

    Collaboration decisions are delegated to ``policy`` (a
    ``core/policy.py::CollabPolicy``): task assignment at admission,
    per-wave escalation actions at retirement, completion feedback.  The
    default ``SpeculativePolicy(threshold=0.6)`` mirrors
    ``CollaborativeEngine``'s historical decision semantics exactly — same
    estimator, threshold, escalation grouping, semantic cache — so greedy
    traces match the per-request engine token for token, on every KV
    layout and model family.  The legacy ``escalation=`` /
    ``escalate_threshold=`` kwargs still work for one release
    (``DeprecationWarning``) and construct the matching policy.

    Policy feature dicts: ``assign`` sees ``{rid, prompt, prompt_len,
    max_new, queue_depth, free_slots, inflight, at_ms, now_ms, wait_ms,
    slo_ms}`` (prompt features + live load stats + REAL deadline state —
    ``wait_ms`` is how long the request has already queued against
    ``slo_ms``); ``feedback`` sees ``{rid, unc, steps, budget, lane,
    ttft_ms, e2e_ms, slo_ms, slo_met, prompt, tokens, draft,
    teacher_topk, domain}`` — ``steps``/``budget`` matching the aligned
    arrays ``decide`` saw for that request (``steps`` is what it actually
    consumed; a stop-token hit makes it < ``budget``), ``lane``
    distinguishing decided actions from lane-assigned completions that
    never reached ``decide``, the latency fields closing the loop for
    SLA/budget policies, and the supervision tape — the served
    ``tokens``, the discarded edge ``draft`` (escalations), the cloud
    ``teacher_topk`` logits when an adaptation loop requested them — all
    host-side already (they rode the wave's single batched device pull).

    Serving knobs: ``clock`` (a ``core/traffic.py`` clock; default
    ``VirtualClock()`` — deterministic modeled ms), ``slo_ms`` (TTFT SLO
    for goodput/attainment in ``stats()`` and the policy features),
    ``prefill_chunk`` (entries above which admission prefills chunked
    across ticks; None = ``tick_tokens``, 0 = always whole-prompt),
    ``stop_token`` (token id that ends a request's edge decode early;
    None = decode the full budget).

    KV layout knobs:
      * ``kv_layout``: "auto" (paged where both models' cache families
        support it, else dense), "paged", or "dense".  Recurrent-state
        families always keep dense (stacked) storage — their state has no
        sequence axis to page.
      * ``kv_block_size``: tokens per block (paged).
      * ``kv_blocks``: total pool blocks incl. the trap (paged).  Default
        sizes the pool to the dense worst case; give a smaller pool to cap
        KV memory — admission is deferred when it runs full.

    ``spans``: a ``core/spans.py`` ``SpanLog`` that records each drain-loop
    iteration as an ``engine.tick`` span with its steps and counters, or
    None (the default: nothing is recorded).
    """

    def __init__(self, edge_model, cloud_model, *, batch_size: int = 8,
                 gamma: int = 4, temperature: float = 0.0,
                 escalate_threshold: Optional[float] = None,
                 estimator: str = "entropy",
                 escalation: Optional[str] = None, policy=None,
                 use_cache: bool = True,
                 cache_threshold: float = 0.95, skeleton_len: int = 8,
                 tick_tokens: int = 16, seed: int = 0,
                 kv_layout: str = "auto", kv_block_size: int = 32,
                 kv_blocks: Optional[int] = None, clock=None,
                 slo_ms: Optional[float] = None,
                 prefill_chunk: Optional[int] = None,
                 stop_token: Optional[int] = None,
                 spec_mode: Optional[str] = None,
                 spec_tree_width: Optional[int] = None,
                 spec_exit_layer: Optional[int] = None,
                 mesh=None, adaptation=None, spans=None):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        if tick_tokens < 1:
            raise ValueError(f"tick_tokens must be >= 1, got {tick_tokens}")
        if kv_block_size < 1:
            raise ValueError(f"kv_block_size must be >= 1, got "
                             f"{kv_block_size}")
        if prefill_chunk is not None and prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0 (0 = whole-prompt "
                             f"prefill), got {prefill_chunk}")
        self.policy = resolve_policy(policy, escalation, escalate_threshold)
        self.kv_layout = resolve_kv_layout(edge_model, cloud_model, kv_layout)
        self.kv_block_size = kv_block_size
        self.kv_blocks = kv_blocks
        self.edge_model = edge_model
        self.cloud_model = cloud_model
        self.batch_size = batch_size
        self.gamma = gamma
        self.temperature = temperature
        self.skeleton_len = skeleton_len
        self.tick_tokens = tick_tokens
        self.seed = seed
        self.clock = clock if clock is not None else VirtualClock()
        self.slo_ms = slo_ms
        self.stop_token = stop_token
        # prompts with more than this many ENTRIES prefill chunked across
        # ticks; None = auto (tick_tokens, so prefill work per tick is
        # bounded by decode work per tick); 0 = always whole-prompt
        self.prefill_chunk = tick_tokens if prefill_chunk is None \
            else prefill_chunk
        self._esc_fns = {"cloud": self._cloud_escalate,
                         "skeleton": self._skeleton_escalate,
                         "speculative": self._spec_escalate}
        # mesh serving: edge drafts are DATA-parallel (batch slots split
        # over the data axes, params replicated); the cloud verifier is
        # TENSOR-parallel over 'model' (params sharded by the launch/
        # sharding.py rules).  Escalation groups are replicated over the
        # data axes (gather_wave hands every data shard the full wave), so
        # the cloud lane never data-shards its pools.
        self.mesh = mesh
        if mesh is not None:
            dp = 1
            for a in mesh.axis_names:
                if a != "model":
                    dp *= mesh.shape[a]
            self._data_shards = dp if batch_size % dp == 0 else 1
        else:
            self._data_shards = 1
        self.edge = Lane(edge_model, estimator, temperature,
                         layout=layout_for(edge_model, self.kv_layout),
                         block_size=kv_block_size, mesh=mesh,
                         data_shards=self._data_shards)
        self.cloud = Lane(cloud_model, estimator, temperature,
                          layout=layout_for(cloud_model, self.kv_layout),
                          block_size=kv_block_size, mesh=mesh)
        self.cache = SemanticCache(threshold=cache_threshold) if use_cache \
            else None
        # online adaptation (core/adaptation.py AdaptationLoop or None):
        # completions feed its FeedbackStore from _finish, and the drain
        # loop offers it a hot-swap point between ticks.  adaptation=None
        # keeps every path byte-identical to the pre-adaptation engine.
        self.adaptation = adaptation
        if adaptation is not None:
            adaptation.bind(edge_model)
        self.spans = spans
        # speculation lane: engine kwarg > policy attribute > linear.  A
        # model family the requested lane cannot serve falls back to the
        # linear tape; the EFFECTIVE mode is what stats()["spec_mode"]
        # reports, so callers can detect the downgrade.
        mode = spec_mode if spec_mode is not None \
            else getattr(self.policy, "spec_mode", None) or "linear"
        if mode not in ("linear", "tree", "self"):
            raise ValueError(f"unknown spec_mode {mode!r}; "
                             "known: linear | tree | self")
        width = spec_tree_width if spec_tree_width is not None \
            else getattr(self.policy, "spec_tree_width", None) or 2
        exit_layer = spec_exit_layer if spec_exit_layer is not None \
            else getattr(self.policy, "spec_exit_layer", None)
        if mode == "tree" and not BatchedSpecDecoder.tree_supported(
                edge_model, cloud_model):
            mode = "linear"
        if mode == "self" and not BatchedSpecDecoder.self_supported(
                edge_model):
            mode = "linear"
        self.spec_mode = mode
        if mode == "tree":
            from repro.core.tree_speculation import branching_for
            self.spec = BatchedSpecDecoder(
                edge_model, cloud_model, gamma=gamma,
                temperature=temperature, mode="tree",
                branching=branching_for(width, gamma))
        elif mode == "self":
            self.spec = BatchedSpecDecoder(
                edge_model, edge_model, gamma=gamma,
                temperature=temperature, mode="self",
                exit_layer=exit_layer)
        else:
            self.spec = BatchedSpecDecoder(edge_model, cloud_model,
                                           gamma=gamma,
                                           temperature=temperature,
                                           kv_layout=self.kv_layout)
        # tree/self SpecOps always run dense per-slot caches (block-masked
        # extends are a dense-layout feature), so their escalation groups
        # build DENSE side states even when the serving lanes are paged.
        # Linear groups keep using the serving lanes — byte-identical.
        # Lane.dense_side() owns the layout decision (rule R4: the
        # scheduler never compares `.layout`); it is identity on lanes
        # that are already dense.
        self._spec_edge = self.edge if mode == "linear" \
            else self.edge.dense_side()
        self._spec_cloud = self.cloud.dense_side() if mode == "tree" \
            else self.cloud
        self._queue: collections.deque = collections.deque()
        self._next_rid = 0
        # intra-batch dedup: in-flight leaders and their coalesced followers
        self._leaders: List[Tuple[np.ndarray, int]] = []
        self._followers: Dict[int, List[_Request]] = {}
        self._kv_stats: Dict[str, Any] = {}
        self._swapped: Dict[int, dict] = {}
        self._preempts = 0
        self._prefill_jobs: Dict[int, dict] = {}    # slot -> chunked job
        self._events: Dict[int, dict] = {}          # rid -> lifecycle stamps

    # ------------------------------------------------------------ submit
    def submit(self, prompt, max_new: int, at: Optional[float] = None,
               domain: Optional[int] = None) -> int:
        """Queue a request.  ``at`` is an OPEN-LOOP arrival time in clock
        milliseconds (``core/traffic.py`` generators produce them): the
        request is invisible to admission until the engine's clock reaches
        it.  ``at=None`` (closed-loop) means "already arrived".
        ``domain`` is an optional workload tag carried through to the
        adaptation feedback record (never affects serving)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert prompt.size >= 2, "scheduler needs >= 2 prompt tokens"
        assert max_new >= 1
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(_Request(rid, prompt, max_new, at=at,
                                    domain=domain))
        return rid

    def _note_group(self, *states):
        live = sum(s.peak_bytes for s in states)
        self._kv_stats["kv_group_peak_bytes"] = max(
            self._kv_stats.get("kv_group_peak_bytes", 0), live)

    # ------------------------------------------------------------ dedup
    def _match_leader(self, key: np.ndarray) -> Optional[int]:
        """rid of an in-flight request whose cache key matches ``key`` at
        the semantic-cache threshold (cosine), else None."""
        if not self._leaders:
            return None
        u = SemanticCache._norm(key)
        for lk, rid in self._leaders:
            if float(u @ lk) >= self.cache.threshold:
                return rid
        return None

    # ------------------------------------------------------------ run
    def run(self, edge_params, cloud_params) -> Dict[int, RequestTrace]:
        """Drain the queue; returns {rid: RequestTrace} for this drain.
        Open-loop: requests with a future ``at`` stay invisible until the
        engine's clock reaches them (idle gaps are jumped/slept over).

        With ``mesh=...`` the drain runs inside a ``runtime.mesh_context``:
        edge params are pinned replicated, cloud params tensor-parallel per
        ``launch/sharding.py``, and every jit traced during the drain picks
        up the mesh (activation constraints, ``gather_wave`` collectives).
        ``mesh=None`` takes the exact pre-mesh path — no context, no
        placement, no constraint ops in any trace."""
        if self.mesh is None:
            return self._run_impl(edge_params, cloud_params)
        from repro import runtime
        from repro.launch.sharding import (params_shardings,
                                           replicated_shardings)
        edge_params = jax.device_put(
            edge_params, replicated_shardings(edge_params, self.mesh))
        cloud_params = jax.device_put(
            cloud_params, params_shardings(cloud_params, self.mesh,
                                           self.cloud_model.cfg))
        with runtime.mesh_context(self.mesh):
            res = self._run_impl(edge_params, cloud_params)
        self._kv_stats["mesh_devices"] = self.mesh.size
        self._kv_stats["mesh_shape"] = {k: int(v)
                                        for k, v in self.mesh.shape.items()}
        return res

    @hot_path
    def _run_impl(self, edge_params, cloud_params) -> Dict[int, RequestTrace]:
        if not self._queue:
            return {}
        # adaptation persists ACROSS drains: pick up from the last
        # hot-swapped edge weights, not the caller's baseline
        if self.adaptation is not None:
            edge_params = self.adaptation.current(edge_params)
        clock = self.clock
        t0 = clock.now()
        for r in self._queue:
            if r.at is None:
                r.at = t0
        # strict ARRIVAL order (ties by rid) — closed-loop batches all
        # "arrive" at run start, so their submission order is unchanged
        self._queue = collections.deque(
            sorted(self._queue, key=lambda r: (r.at, r.rid)))
        B = self.batch_size
        # slot capacity: prompt + generation + speculative overdraft margin
        # (matches SpecDecoder's max_seq so escalation reuses the same
        # pads; a tree lane overdrafts a full padded tree per round)
        ovr = self.spec.plan.n_pad if self.spec_mode == "tree" else self.gamma
        self._slot_len = max(r.prompt.size + r.max_new for r in self._queue) \
            + 2 * max(ovr, 16) + 8
        self._kv_stats = {"kv_layout": self.kv_layout}
        state = self.edge.make_state(edge_params, B, self._slot_len,
                                     num_blocks=self.kv_blocks)
        tok = jnp.zeros((B, 1, 1), jnp.int32)
        steps = jnp.zeros((B,), jnp.int32)
        unc = jnp.zeros((B,), jnp.float32)
        # host mirrors of tok/steps/unc, exact between ticks: every device
        # update is either host-originated (admit/finalize/swap, mirrored
        # below) or covered by the ONE batched device_get after each tick —
        # so admission, victim picking and swap-out never touch the device
        # (rule R1: zero per-slot host syncs on the hot path)
        tok_h = np.zeros((B,), np.int32)
        steps_h = np.zeros((B,), np.int32)
        unc_h = np.zeros((B,), np.float32)
        slots = [_Slot() for _ in range(B)]
        rng = jax.random.PRNGKey(self.seed)
        results: Dict[int, RequestTrace] = {}
        self._leaders, self._followers = [], {}
        self._swapped: Dict[int, dict] = {}     # rid -> host swap handle
        self._preempts = 0
        self._prefill_jobs = {}                 # slot -> detached chunk job
        self._events = {r.rid: {"submit_ms": float(r.at),
                                "swaps": 0, "defers": 0}
                        for r in self._queue}
        stop = jnp.int32(-1 if self.stop_token is None else self.stop_token)
        # one engine.tick span per iteration; its steps are boundary marks
        # (sp.mark closes the previous step), so the loop keeps its shape
        sp = self.spans
        if sp is not None:
            sp.bind(clock)

        while self._queue or self._swapped or any(s.req is not None
                                                  for s in slots):
            if sp is not None:
                sp.begin("engine.tick")
            # ---- online-adaptation hot-swap point: BETWEEN ticks, the
            # loop offers the current edge weights for replacement.  The
            # swap is a pure pytree rebind — same treedef/shapes/dtypes —
            # so in-flight caches stay valid and no jitted function sees a
            # new cache key (steady_state_recompiles == 0 across a swap)
            if self.adaptation is not None:
                swapped_p = self.adaptation.maybe_update(edge_params)
                if swapped_p is not None:
                    edge_params = swapped_p
                    state.rebind(edge_params)
            if sp is not None:
                sp.mark("sched.admit")
            free = [b for b in range(B) if slots[b].req is None]
            wave: set = set()       # slots admitted/resumed this wave
            stalled = False
            # ---- resume swapped-out victims first: every victim predates
            # everything still queued, so strict arrival order = swapped
            # before queue (see the anti-starvation policy above)
            while self._swapped and free:
                rid0 = min(self._swapped)
                b = free[0]
                if not state.swap_in(b, self._swapped[rid0]["kv"]):
                    stalled = True  # pool still tight; retry next tick
                    break
                h = self._swapped.pop(rid0)
                free.pop(0)
                wave.add(b)
                slots[b] = h["slot"]
                tok = tok.at[b, 0, 0].set(h["tok"])
                steps = steps.at[b].set(h["steps"])
                unc = unc.at[b].set(h["unc"])
                tok_h[b], steps_h[b], unc_h[b] = \
                    h["tok"], h["steps"], h["unc"]
            # ---- admit queued requests into free slots (batched cache
            # probe).  A stalled swap-in blocks NEW admissions entirely:
            # the victim predates every queued request, so letting
            # newcomers consume the blocks it is waiting for would break
            # strict arrival order (it resumes within a bounded number of
            # ticks as in-flight slots retire).
            # ---- admission probe: pop every ARRIVED request in a bounded
            # window (free slots + one batch) INDEPENDENTLY of free edge
            # slots.  Cache hits, coalesced followers and cloud-lane
            # requests are served without ever occupying a slot, so a full
            # edge batch no longer head-of-line-blocks them behind slot
            # availability; slot-needing requests that find none simply go
            # back to the queue head.  A stalled swap-in still blocks new
            # admissions entirely: the victim predates every queued
            # request, so letting newcomers consume the blocks it waits
            # for would break strict arrival order.
            deferred = False
            cloud_wave: List[_Request] = []
            now = clock.now()
            if self._queue and not stalled:
                cands: List[_Request] = []
                while self._queue and len(cands) < len(free) + B \
                        and self._queue[0].at <= now:
                    cands.append(self._queue.popleft())
                hits: List[Optional[Any]] = [None] * len(cands)
                if self.cache is not None and cands:
                    for r in cands:
                        if r.key is None:
                            r.key = embed_tokens_mean(self.edge_model,
                                                      edge_params, r.prompt)
                    hits = self.cache.lookup_batch(
                        np.stack([r.key for r in cands]))
                putback: List[_Request] = []
                pend_keys: List[np.ndarray] = []
                share = state.share_hints([r.prompt for r in cands])

                def stay(r):
                    # r stays queued; any matching request probed later
                    # this wave must stay BEHIND it (pend_keys), or the
                    # sequential cache/coalesce semantics would serve a
                    # younger twin ahead of its would-be leader
                    putback.append(r)
                    if r.key is not None:
                        pend_keys.append(SemanticCache._norm(r.key))

                bs, lasts, news = [], [], []
                for r, hit, sharable in zip(cands, hits, share):
                    if deferred:
                        putback.append(r)   # pool pressure aborts the wave
                        continue
                    if pend_keys and r.key is not None and any(
                            float(SemanticCache._norm(r.key) @ k)
                            >= self.cache.threshold for k in pend_keys):
                        stay(r)
                        continue
                    if hit is not None:
                        ev = self._events[r.rid]
                        ev["first_token_ms"] = ev["retire_ms"] = now
                        ev["first_token_ready_ms"] = now
                        ev["path"], ev["tokens"] = "cache", len(hit)
                        results[r.rid] = RequestTrace("cache",
                                                      tokens=list(hit))
                        continue
                    if self.cache is not None:
                        # coalesce with an identical in-flight request: the
                        # sequential engine's later twin would hit the
                        # semantic cache the leader is about to warm
                        lid = self._match_leader(r.key)
                        if lid is not None:
                            self._followers.setdefault(lid, []).append(r)
                            self.cache.hits += 1
                            continue
                    # task assignment: the policy picks this request's lane
                    # from prompt features + live load + REAL deadline
                    # state — ONCE per request (a deferred request keeps
                    # its lane, so stateful policies never see phantom
                    # duplicates)
                    if r.lane is None:
                        r.lane = self.policy.assign({
                            "rid": r.rid, "prompt": r.prompt,
                            "prompt_len": int(r.prompt.size),
                            "max_new": int(r.max_new),
                            "queue_depth": len(self._queue),
                            "free_slots": len(free),
                            "inflight": sum(s.req is not None
                                            for s in slots),
                            "at_ms": float(r.at), "now_ms": now,
                            "wait_ms": now - float(r.at),
                            "slo_ms": self.slo_ms})
                        if r.lane not in LANES:
                            raise ValueError(
                                f"policy {self.policy.name!r} assigned "
                                f"unknown lane {r.lane!r}; known: "
                                f"{' | '.join(LANES)}")
                    if r.lane == "cloud":
                        # cloud-only: no edge slot needed — one grouped
                        # batched cloud generation below (grouped shapes
                        # pad to batch_size, so a wave takes at most B).
                        # Register as a leader so identical prompts later
                        # in this wave coalesce instead of paying a second
                        # cloud generation (resolved in _finish this wave)
                        if len(cloud_wave) < B:
                            if self.cache is not None:
                                self._leaders.append(
                                    (SemanticCache._norm(r.key), r.rid))
                            cloud_wave.append(r)
                        else:
                            stay(r)
                        continue
                    if not free:
                        stay(r)             # collab/edge: needs a slot
                        continue
                    b = free.pop(0)
                    need = r.prompt.size - 1 + r.max_new
                    # long prompts reserve now and prefill DETACHED, one
                    # chunk per tick, landing via finalize — never stalling
                    # the in-flight batch behind a monolithic prefill.
                    # Prompts the layout flags as sharable take the
                    # monolithic path: a chunked begin defers the prefix
                    # index registration until finalize, which would cost
                    # same-wave twins their block sharing
                    chunked = (0 < self.prefill_chunk < r.prompt.size - 1
                               and not sharable)
                    admit = state.begin if chunked else state.admit
                    ok = admit(b, r.prompt, need)
                    if not ok and not state.fits_empty(need):
                        # private footprint exceeds the whole pool: only
                        # live prefix sharing can admit this request, and
                        # preemption could evict the very blocks that
                        # sharing needs — defer instead of swapping, and
                        # fail fast once even sharing cannot cover it
                        if not state.fits_empty(need, r.prompt):
                            raise RuntimeError(
                                f"request {r.rid} needs more KV blocks "
                                "than the whole pool; raise kv_blocks")
                    else:
                        while not ok:
                            # pool full: preempt-by-swap — swap out the
                            # victim holding its reservation longest,
                            # retry until admitted or out of victims
                            v = self._pick_victim(state, slots, steps_h,
                                                  wave)
                            if v is None:
                                break
                            vreq = slots[v].req
                            # the victim's decode scalars come from the
                            # host mirrors — swap-out costs zero extra
                            # device syncs (the blocks themselves move
                            # via state.swap_out's one batched pull)
                            self._swapped[vreq.rid] = {
                                "kv": state.swap_out(v),
                                "slot": slots[v],
                                "tok": int(tok_h[v]),
                                "steps": int(steps_h[v]),
                                "unc": float(unc_h[v]),
                            }
                            self._events[vreq.rid]["swaps"] += 1
                            slots[v] = _Slot()
                            steps = steps.at[v].set(0)
                            steps_h[v] = 0
                            free.append(v)
                            self._preempts += 1
                            ok = admit(b, r.prompt, need)
                    if not ok:
                        # every preemptable victim is out and the pool is
                        # still too tight: defer this and the rest, keep
                        # arrival order (in-flight retirements will free
                        # blocks within a bounded number of ticks)
                        free.insert(0, b)
                        self._events[r.rid]["defers"] += 1
                        putback.append(r)
                        deferred = True
                        continue
                    slots[b] = _Slot(req=r)
                    wave.add(b)
                    self._events[r.rid]["admit_ms"] = now
                    if chunked:
                        self._prefill_jobs[b] = self.edge.start_prefill(
                            edge_params, r.prompt,
                            state.detached_len(r.prompt.size - 1),
                            self.prefill_chunk)
                    else:
                        clock.on_prefill(r.prompt.size - 1)
                        self._events[r.rid]["prefill_done_ms"] = now
                        bs.append(b)
                        lasts.append([[int(r.prompt[-1])]])
                        news.append(r.max_new)
                    if self.cache is not None:
                        self._leaders.append((SemanticCache._norm(r.key),
                                              r.rid))
                for r in reversed(putback):
                    self._queue.appendleft(r)
                if bs:
                    idx = jnp.asarray(bs, jnp.int32)
                    tok = tok.at[idx].set(jnp.asarray(lasts, jnp.int32))
                    steps = steps.at[idx].set(jnp.asarray(news, jnp.int32))
                    unc = unc.at[idx].set(0.0)
                    tok_h[bs] = [l[0][0] for l in lasts]
                    steps_h[bs] = news
                    unc_h[bs] = 0.0

            if cloud_wave:
                # cloud-assigned lane: one grouped batched cloud generation
                # for the wave (task assignment at admission).  First-token
                # time is the generation's own first step, not the (later)
                # group completion
                rng, r_ = jax.random.split(rng)
                t_cw = clock.now()
                tk = self.adaptation.capture_topk \
                    if self.adaptation is not None else 0
                toks = self._group_generate(
                    self.cloud, cloud_params,
                    [q.prompt for q in cloud_wave],
                    [q.max_new for q in cloud_wave], r_, topk=tk)
                teach = [None] * len(cloud_wave)
                if tk:
                    toks, teach = toks
                for q, t, th in zip(cloud_wave, toks, teach):
                    self._finish(results, q, RequestTrace(
                        "cloud", cloud_passes=q.max_new, tokens=t,
                        teacher_topk=th),
                        t_first=t_cw + clock.step_ms)

            # ---- advance chunked prefills: one detached chunk per job per
            # tick, interleaved with the batch's decode; a finished job
            # lands its cache (finalize) and arms the slot for decode
            if sp is not None:
                sp.mark("prefill.advance", jobs=len(self._prefill_jobs))
            for b in list(self._prefill_jobs):
                job = self._prefill_jobs[b]
                before = job["done"]
                finished = self.edge.advance_prefill(edge_params, job)
                clock.on_prefill(job["done"] - before)
                if sp is not None:
                    sp.count(tokens=job["done"] - before)
                if finished:
                    state.finalize(b, job["cache"])
                    del self._prefill_jobs[b]
                    r = slots[b].req
                    ev = self._events[r.rid]
                    ev["prefill_done_ms"] = clock.now()
                    ev["prefill_chunks"] = -(-job["done"]
                                             // self.prefill_chunk)
                    tok = tok.at[b, 0, 0].set(int(r.prompt[-1]))
                    steps = steps.at[b].set(r.max_new)
                    unc = unc.at[b].set(0.0)
                    tok_h[b] = int(r.prompt[-1])
                    steps_h[b] = r.max_new
                    unc_h[b] = 0.0

            occupied = [b for b in range(B) if slots[b].req is not None]
            if not occupied:
                if deferred:
                    raise RuntimeError(
                        "paged KV pool too small for the queued request "
                        "even with an empty batch; raise kv_blocks")
                if stalled:
                    rid0 = min(self._swapped)
                    raise RuntimeError(
                        f"paged KV pool cannot restore swapped-out request "
                        f"{rid0} even with an empty batch (its blocks + "
                        "outstanding reservation exceed the pool); raise "
                        "kv_blocks")
                if sp is not None:
                    sp.finish()
                if self._queue:
                    # nothing in flight and every queued arrival is in the
                    # future: jump/sleep the clock to the next arrival
                    clock.wait_until(float(self._queue[0].at))
                continue            # all cache hits / cloud completions
            if sp is not None:
                sp.mark("kv.flush")
            state.flush()

            # ---- one batched decode tick (pow2-bucketed step count: the
            # scan recompiles per static n_steps, so bucketing bounds the
            # compile set; overshoot decodes masked garbage).  The live
            # step budget comes from the HOST MIRROR — no pre-tick sync
            # repro-lint: ok(R1, steps_h is the numpy host mirror - no device pull)
            live = int(steps_h[occupied].max())
            if live <= 0:
                if sp is not None:
                    sp.finish()
                continue            # every occupied slot is mid-prefill
            n = pow2_steps(min(self.tick_tokens, live), self.tick_tokens)
            if sp is not None:
                sp.mark("kv.prepare_tick")
            state.prepare_tick(occupied, steps_h, n)
            if sp is not None:
                sp.mark("edge.dispatch", n_steps=n)
            rng, r = jax.random.split(rng)
            state.caches, tok, steps, unc, toks, actives = self.edge._chunk(
                edge_params, state.caches, tok, steps, unc, r, stop,
                n_steps=n)
            state.advance(n)
            clock.on_steps(n)
            t_tick = clock.now()
            # THE host readback: one batched explicit pull per tick covers
            # retirement (steps/unc), the emitted streams (toks/actives)
            # and the carry mirror (tok == last scan emission)
            if sp is not None:
                sp.mark("edge.readback")
            steps_d, unc_d, toks_h, act_h = jax.device_get(  # repro-lint: ok(R1, the single batched per-tick device pull)
                (steps, unc, toks, actives))
            t_ready = clock.now()
            if sp is not None:
                sp.mark("sched.retire")
            steps_h = np.array(steps_d)     # device_get views are
            unc_h = np.array(unc_d)         # read-only; mirrors mutate
            tok_h = np.array(toks_h[-1])
            for b in occupied:
                new = [int(t) for t, a in zip(toks_h[:, b], act_h[:, b])
                       if a]
                if new and not slots[b].tokens:
                    # tick-granular first-token stamps: the emitting tick's
                    # dispatch, and its readback (the host holds the token);
                    # escalated requests are re-stamped in _finish
                    ev = self._events[slots[b].req.rid]
                    ev["first_token_ms"] = t_tick
                    ev["first_token_ready_ms"] = t_ready
                slots[b].tokens.extend(new)

            # ---- retire finished slots; the policy names each one's action
            # (steps_h/unc_h are this tick's batched pull — already host)
            retiring: List[Tuple[_Request, float, List[int]]] = []
            for b in occupied:
                if steps_h[b] > 0 or b in self._prefill_jobs:
                    continue
                req = slots[b].req
                # steps actually spent: every ACTIVE emission appended one
                # token, and a stop-token hit zeroes the budget early — so
                # spent < max_new is a real state decide/feedback must see
                req.spent = min(len(slots[b].tokens), req.max_new)
                u = float(unc_h[b]) / max(req.spent, 1)
                retiring.append((req, u, slots[b].tokens[:req.spent]))
                slots[b] = _Slot()
                state.retire(b)

            if retiring:
                # one vectorized decide over the wave's collaborative
                # requests; edge-assigned ones force-accept their output.
                # steps = what each request actually consumed (early stop
                # makes it < budget); budget = its max_new grant — distinct
                # arrays, no aliasing
                actions = ["accept"] * len(retiring)
                decided = [i for i, (rq, _, _) in enumerate(retiring)
                           if rq.lane != "edge"]
                if decided:
                    acts = list(self.policy.decide(
                        np.array([retiring[i][1] for i in decided],
                                 np.float32),
                        np.array([retiring[i][0].spent
                                  for i in decided], np.int32),
                        np.array([retiring[i][0].max_new
                                  for i in decided], np.int32)))
                    if len(acts) != len(decided):
                        raise ValueError(
                            f"policy {self.policy.name!r} decided "
                            f"{len(acts)} actions for a wave of "
                            f"{len(decided)}")
                    for i, a in zip(decided, acts):
                        a = str(a)
                        if a not in ACTIONS:
                            raise ValueError(
                                f"policy {self.policy.name!r} decided "
                                f"unknown action {a!r}; known: "
                                f"{' | '.join(ACTIONS)}")
                        actions[i] = a
                groups: Dict[str, List[Tuple[_Request, float]]] = {}
                for (req, u, toks), a in zip(retiring, actions):
                    if a == "accept":
                        self._finish(results, req, RequestTrace(
                            "edge", edge_calls=req.spent, uncertainty=u,
                            tokens=toks))
                    else:
                        # edge tokens are discarded from the CLIENT stream
                        # — escalation regenerates with cloud involvement
                        # (same as the reference engine) — but kept on the
                        # request as the rejected draft: with the cloud's
                        # corrected continuation it completes the
                        # (prompt, draft, correction) supervision triple
                        req.draft = toks
                        groups.setdefault(a, []).append((req, u))
                # one batched group per decided action (a wave can mix).
                # The escalation's own first step is the client-visible
                # first token (the edge stream it replaces was discarded)
                for a, grp in groups.items():
                    rng, r = jax.random.split(rng)
                    t_esc = clock.now()
                    for req, tr in self._esc_fns[a](
                            edge_params, cloud_params,
                            [g[0] for g in grp], [g[1] for g in grp], r):
                        self._finish(results, req, tr,
                                     t_first=t_esc + clock.step_ms)
            if sp is not None:
                sp.finish(slots_occupied=len(occupied),
                          slots_decoding=sum(b not in self._prefill_jobs
                                             for b in occupied),
                          slot_steps_active=np.count_nonzero(act_h),
                          slot_steps_run=n * B,
                          **state.attn_blocks(n, act_h))

        self._kv_stats["kv_peak_bytes"] = state.peak_bytes
        self._kv_stats["kv_capacity_bytes"] = state.capacity_bytes
        self._kv_stats["preemptions"] = self._preempts
        self._kv_stats.update(state.stats())
        return results

    @hot_path
    def _pick_victim(self, state, slots, steps_h, wave) -> Optional[int]:
        """Preemption victim by a cost model: score each candidate by the
        decode steps its eviction frees (remaining budget — how long it
        would hold its block reservation) per block of KV it has staged
        (``steps / (1 + owned_blocks)`` — swap-out checkpoints those bytes
        to host and swap-in restores them, so a fat slot is an expensive
        victim even when it has far to go).  Layouts without a block pool
        report ``owned_blocks == 0`` (the ``SequenceState`` protocol
        query — rule R4 forbids probing pool internals here), so the
        score degrades to raw remaining steps — the historic most-steps
        ordering — and ties still break toward the youngest request.
        ``steps_h`` is the run loop's HOST mirror, so scoring costs no
        device sync (rule R1).  Slots admitted or resumed in the current
        wave are exempt — their staged device writes have not flushed
        yet, and exempting them prevents same-tick swap thrash.  Slots
        whose swap-in restore could never fit the pool (admitted over a
        prefix larger than their private footprint allows) are exempt too
        — swapping them would strand their completed work.  So are slots
        mid-chunked-prefill: their device blocks hold garbage until
        finalize, and swapping would checkpoint that garbage."""
        best = None
        for b, s in enumerate(slots):
            if s.req is None or b in wave or b in self._prefill_jobs \
                    or not state.swappable(b):
                continue
            key = (float(steps_h[b]) / (1.0 + state.owned_blocks(b)),
                   int(steps_h[b]), s.req.rid)
            if best is None or key > best[0]:
                best = (key, b)
        return None if best is None else best[1]

    def serve_batch(self, edge_params, cloud_params, prompts,
                    max_new, domains=None) -> List[RequestTrace]:
        """Convenience: submit ``prompts``, drain, return traces in order.
        ``max_new`` may be an int or a per-request sequence; ``domains``
        an optional per-request workload-tag sequence (adaptation)."""
        if isinstance(max_new, int):
            max_new = [max_new] * len(prompts)
        if len(max_new) != len(prompts):
            raise ValueError(f"{len(prompts)} prompts but {len(max_new)} "
                             "max_new budgets")
        if domains is None:
            domains = [None] * len(prompts)
        if len(domains) != len(prompts):
            raise ValueError(f"{len(prompts)} prompts but {len(domains)} "
                             "domain tags")
        rids = [self.submit(p, m, domain=d)
                for p, m, d in zip(prompts, max_new, domains)]
        results = self.run(edge_params, cloud_params)
        return [results[rid] for rid in rids]

    # ------------------------------------------------------------ internals
    def _finish(self, results, req: _Request, tr: RequestTrace, *,
                t_first: Optional[float] = None):
        """Complete ``req``: stamp lifecycle events, fire policy feedback,
        warm the cache, resolve followers.  ``t_first`` overrides the
        first-token stamp (escalations/cloud lanes — their client stream
        starts with the regeneration, not the discarded edge decode); the
        host holds that stream only from the wave's return, now."""
        now = self.clock.now()
        ev = self._events.setdefault(
            req.rid, {"submit_ms": now, "swaps": 0, "defers": 0})
        if t_first is not None:
            ev["first_token_ms"] = t_first
            ev["first_token_ready_ms"] = now
        elif "first_token_ms" not in ev:
            ev["first_token_ms"] = ev["first_token_ready_ms"] = now
        ev["retire_ms"] = now
        ev["path"] = tr.path
        ev["tokens"] = len(tr.tokens) if tr.tokens else 0
        if tr.path != "cache":
            # completion feedback: realized quality proxy + cloud-token
            # cost close the loop for learning (bandit/budget) policies.
            # features carry the request's lane so policies can tell a
            # decided action from a lane-assigned completion (which never
            # went through decide), plus the realized deadline outcome so
            # SLA policies reconcile against REAL latencies, not proxies
            ttft = ev["first_token_ms"] - ev["submit_ms"]
            slo_met = self.slo_ms is None or ttft <= self.slo_ms
            # the corrected token tape (and teacher top-k, when the wave
            # already paid for the cloud pass) rides the feedback payload:
            # policies used to see only the scalar quality proxy while the
            # continuation itself was dropped on the floor.  Everything
            # here is already host-side — it came off the wave's single
            # batched device_get — so threading it costs zero extra syncs
            self.policy.feedback(
                "accept" if tr.path == "edge" else tr.path,
                trace_quality(tr, req.max_new),
                cloud_tokens(tr, self.gamma),
                {"rid": req.rid, "unc": tr.uncertainty,
                 "steps": req.spent if req.spent else req.max_new,
                 "budget": req.max_new, "lane": req.lane,
                 "ttft_ms": ttft, "e2e_ms": now - ev["submit_ms"],
                 "slo_ms": self.slo_ms, "slo_met": slo_met,
                 "prompt": req.prompt, "tokens": tr.tokens,
                 "draft": req.draft, "teacher_topk": tr.teacher_topk,
                 "domain": req.domain})
            if self.adaptation is not None and tr.tokens:
                self.adaptation.observe(
                    prompt=req.prompt, tokens=tr.tokens, draft=req.draft,
                    teacher_topk=tr.teacher_topk, domain=req.domain,
                    sla="none" if self.slo_ms is None
                    else ("met" if slo_met else "missed"),
                    path=tr.path)
        if self.cache is not None and tr.tokens is not None \
                and req.key is not None:
            self.cache.insert(req.key, tr.tokens)
        results[req.rid] = tr
        # resolve coalesced followers from the leader's result (the
        # sequential engine would serve them from the just-warmed cache)
        self._leaders = [(k, rid) for k, rid in self._leaders
                         if rid != req.rid]
        for f in self._followers.pop(req.rid, []):
            fev = self._events.setdefault(
                f.rid, {"submit_ms": now, "swaps": 0, "defers": 0})
            fev.setdefault("first_token_ms", now)
            fev.setdefault("first_token_ready_ms", now)
            fev["retire_ms"] = now
            fev["path"], fev["tokens"] = "cache", ev["tokens"]
            results[f.rid] = RequestTrace(
                "cache", tokens=list(tr.tokens) if tr.tokens else None)

    @hot_path
    def _group_generate(self, lane: Lane, params, prompts,
                        max_news: List[int], rng, topk: int = 0):
        """Batched greedy/sampled generation for an escalation group: per-
        request prefill, then ONE decode scan over the padded group.  The
        initial tok/steps state is host-built and uploaded once; the only
        readback is the single batched pull of the emitted tape (rule
        R1).  Returns the per-request token lists; with ``topk > 0`` the
        scan additionally emits top-k teacher logits and the return
        becomes ``(tokens, teachers)`` where ``teachers[i]`` is a
        ``(values, indices)`` pair trimmed to request ``i``'s emitted
        length — capture extends the SAME batched pull, never adds one."""
        if max(max_news) == 0:
            empty = [[] for _ in prompts]
            return (empty, [None] * len(prompts)) if topk else empty
        n = pow2_steps(max(max_news), 1 << 30)      # bound scan compiles
        G = self.batch_size                         # pad: stable jit shapes
        need = [len(p) - 1 + m for p, m in zip(prompts, max_news) if m > 0]
        state = lane.make_state(params, G, self._slot_len, need_tokens=need)
        tok_h = np.zeros((G, 1, 1), np.int32)
        steps_h = np.zeros((G,), np.int32)
        members = []
        for i, (p, m) in enumerate(zip(prompts, max_news)):
            if m <= 0:
                continue
            state.admit(i, p, len(p) - 1 + m)
            self.clock.on_prefill(len(p) - 1)
            members.append(i)
            tok_h[i, 0, 0] = int(p[-1])
            steps_h[i] = m
        state.flush()
        state.prepare_tick(members, steps_h, n)
        # escalation/cloud groups never stop early: their budgets come
        # from the retirement wave, so stop stays disarmed (-1)
        outs = lane._chunk(
            params, state.caches, jnp.asarray(tok_h), jnp.asarray(steps_h),
            jnp.zeros((G,), jnp.float32), rng, jnp.int32(-1), n_steps=n,
            topk=topk)
        self.clock.on_steps(n)
        self._note_group(state)
        if topk:
            toks, actives, tvals, tidx = outs[4:]
            toks_h, act_h, tv_h, ti_h = jax.device_get(  # repro-lint: ok(R1, the single batched per-group device pull)
                (toks, actives, tvals, tidx))
            tokens = [[int(t) for t, a in zip(toks_h[:, i], act_h[:, i])
                       if a] for i in range(len(prompts))]
            # emissions are a True-prefix of the scan (budgets only count
            # down), so request i's teacher rows are its first len(tokens)
            teachers = [(np.array(tv_h[:len(t), i]),
                         np.array(ti_h[:len(t), i]))
                        for i, t in enumerate(tokens)]
            return tokens, teachers
        toks, actives = outs[4:]
        toks_h, act_h = jax.device_get((toks, actives))  # repro-lint: ok(R1, the single batched per-group device pull)
        return [[int(t) for t, a in zip(toks_h[:, i], act_h[:, i]) if a]
                for i in range(len(prompts))]

    def _cloud_escalate(self, edge_params, cloud_params, reqs, uncs, rng):
        """Grouped full-cloud regeneration (task assignment).  When an
        adaptation loop is attached, the SAME cloud pass also emits top-k
        teacher logits (already paid for — the capture rides the group's
        one batched pull) so the rejected edge draft gets distillation
        supervision."""
        out: List[Tuple[_Request, RequestTrace]] = []
        tk = self.adaptation.capture_topk \
            if self.adaptation is not None else 0
        toks = self._group_generate(self.cloud, cloud_params,
                                    [r.prompt for r in reqs],
                                    [r.max_new for r in reqs], rng,
                                    topk=tk)
        teach = [None] * len(reqs)
        if tk:
            toks, teach = toks
        for r, u, t, th in zip(reqs, uncs, toks, teach):
            out.append((r, RequestTrace(
                "cloud", edge_calls=r.max_new, cloud_passes=r.max_new,
                uncertainty=u, tokens=t, teacher_topk=th)))
        return out

    def _skeleton_escalate(self, edge_params, cloud_params, reqs, uncs, rng):
        """Grouped skeleton division: one batched cloud skeleton pass plus
        one batched edge completion pass for the whole group."""
        out: List[Tuple[_Request, RequestTrace]] = []
        r1, r2 = jax.random.split(rng)
        ks = [min(self.skeleton_len, r.max_new) for r in reqs]
        skels = self._group_generate(self.cloud, cloud_params,
                                     [r.prompt for r in reqs], ks, r1)
        exts = [np.concatenate([r.prompt, np.asarray(s, np.int32)])
                for r, s in zip(reqs, skels)]
        rests = self._group_generate(
            self.edge, edge_params, exts,
            [r.max_new - k for r, k in zip(reqs, ks)], r2)
        for r, u, k, s, rest in zip(reqs, uncs, ks, skels, rests):
            out.append((r, RequestTrace(
                "skeleton", edge_calls=r.max_new + (r.max_new - k),
                cloud_passes=k, uncertainty=u, tokens=s + rest)))
        return out

    @hot_path
    def _spec_escalate(self, edge_params, cloud_params, reqs, uncs, rng):
        """One BatchedSpecDecoder group over all escalated requests.  Paged
        groups pre-grow each slot to prompt + budget + one round of draft
        overdraft — spec rewinds only move ``pos``, never reallocate.
        A tree lane overdrafts a full padded tree per round and runs on the
        dense side lanes; the self lane builds ONE edge-side state (draft
        and verify share cache and params — no cloud involvement, so its
        traces carry ``cloud_passes=0``)."""
        G = self.batch_size
        mode = self.spec_mode
        ovr = (self.spec.plan.n_pad if mode == "tree" else self.gamma) + 2
        need = [r.prompt.size - 1 + r.max_new + ovr for r in reqs]
        d_state = self._spec_edge.make_state(edge_params, G, self._slot_len,
                                             need_tokens=need)
        states = [d_state]
        if mode != "self":
            t_state = self._spec_cloud.make_state(
                cloud_params, G, self._slot_len, need_tokens=need)
            states.append(t_state)
        last_h = np.zeros((G, 1, 1), np.int32)
        for i, (r, nd) in enumerate(zip(reqs, need)):
            for st in states:
                st.admit(i, r.prompt, nd)
            last_h[i, 0, 0] = int(r.prompt[-1])
        last = jnp.asarray(last_h)
        overdraft = np.zeros((G,), np.int32)
        overdraft[:len(reqs)] = [n - (r.prompt.size - 1)
                                 for n, r in zip(need, reqs)]
        for st in states:
            st.flush()
            st.prepare_tick(list(range(len(reqs))), overdraft, 1 << 30)
        max_news = [r.max_new for r in reqs] + [0] * (G - len(reqs))
        for r in reqs:
            self.clock.on_prefill(r.prompt.size - 1)
        if mode == "self":
            outs, stats = self.spec.generate_group_self(
                edge_params, d_state.caches, last, max_news, rng)
        else:
            outs, stats = self.spec.generate_group(
                edge_params, cloud_params, d_state.caches, t_state.caches,
                last, max_news, rng)
        # modeled cost: the group runs the slowest member's rounds, each a
        # draft chunk (gamma steps, or the tree's depth levels) + one
        # verify + one commit step
        draft_steps = self.spec.plan.depth if mode == "tree" else self.gamma
        self.clock.on_steps(max(st["rounds"] for st in stats[:len(reqs)])
                            * (draft_steps + 2))
        self._note_group(*states)
        res = []
        for i, (r, u) in enumerate(zip(reqs, uncs)):
            st = stats[i]
            res.append((r, RequestTrace(
                "speculative",
                edge_calls=r.max_new + st["rounds"] * (draft_steps + 1),
                cloud_passes=0 if mode == "self" else st["rounds"],
                uncertainty=u, tokens=outs[i])))
        return res

    # ------------------------------------------------------------ stats
    @property
    def events(self) -> Dict[int, dict]:
        """Per-request lifecycle events of the last ``run`` (rid ->
        submit/admit/first-token/retire stamps in clock ms, swap + defer
        counts, path, token count).  Also ``prefill_done_ms`` (a chunked
        prefill's finalize, with ``prefill_chunks``; ``admit_ms`` for a
        whole-prompt one) and ``first_token_ready_ms`` (the readback or
        wave return that put the first token on the host; ``first_token_ms``
        is the emitting tick's dispatch)."""
        return self._events

    def stats(self) -> Dict[str, Any]:
        c = self.spec.counters
        return {"cache_hit_rate": self.cache.hit_rate if self.cache else 0.0,
                "policy": self.policy.name,
                "spec_mode": self.spec_mode,
                # acceptance over candidates DRAFTED; emitted per verify
                # pass (>1 is the whole point of the speculation lanes)
                "spec_accept_rate": c["accepted_tokens"] / c["draft_tokens"]
                if c["draft_tokens"] else 0.0,
                "accepted_tokens_per_step":
                c["emitted_tokens"] / c["member_rounds"]
                if c["member_rounds"] else 0.0,
                "spec_lanes": {self.spec_mode: dict(c)},
                **self.policy.stats(), **self._kv_stats,
                **({"adaptation": self.adaptation.stats()}
                   if self.adaptation is not None else {}),
                **latency_rollup(self._events, self.slo_ms)}
