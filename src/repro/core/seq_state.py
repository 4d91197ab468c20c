"""Family-agnostic per-sequence decode state for the serving scheduler.

The scheduler used to special-case every cache family: dense KV slabs,
paged KV block tables, and a per-request snapshot+replay fallback for
recurrent state.  This module hides all of that behind ONE adapter
protocol, so ``core/scheduler.py`` and ``core/speculative.py`` drive every
model family — transformer, moe, ssm (mamba2), hybrid, xlstm — through the
same slot/tick/escalation machinery:

  * ``SequenceState`` — the host-side slot-state owner: ``admit`` (prefill
    + capacity reservation), ``flush`` (batched device writes),
    ``prepare_tick`` (per-tick capacity growth), ``advance`` (the host
    mirror of ``pos`` after a tick), ``retire`` (free), and the
    ``peak_bytes`` / ``capacity_bytes`` / ``stats`` / ``attn_blocks``
    accounting the benchmarks and spans read.  One implementation per layout:

      - ``DenseKV``    — stacked per-slot caches padded to a common
        ``slot_len`` (the parity oracle).
      - ``PagedKV``    — one shared block pool + per-slot block tables
        (``core/paged_cache.py``), with refcounted block-level prefix
        sharing + copy-on-write (``share_prefix`` / ``cow_split``) and
        host-buffer swap (``swap_out`` / ``swap_in``) backing the
        scheduler's preemption path.
      - ``RecurrentState`` — fixed-size recurrent state (ssm/xlstm/hybrid):
        dense stacked storage (there is no sequence axis to page), its own
        class so layout policy stays out of the scheduler.
      - ``PagedState`` — ``PagedKV`` for the attention layers beside one
        row of recurrent state per slot (granitehybrid's Mamba2 layers),
        both in one cache pytree.

  * ``SpecOps`` — the traceable (jit-safe) per-model ops speculative
    decoding composes: ``step`` / ``extend`` for drafting and verification,
    and ``snapshot`` / ``commit`` for the per-round rewind.  KV layouts
    snapshot ``pos`` and commit with a ``pos`` write; the recurrent layout
    snapshots the state pytree (a reference, not a copy — snapshot-free on
    the host) and commits by replaying each slot's accepted prefix through
    the model's batched ``replay_step`` (padded draft tape + per-slot
    ``jnp.where`` state select), replacing the old host-side per-request
    snapshot+replay fallback.

  * ``Lane`` — the per-model jitted machinery (batched decode step,
    per-prompt-length prefill, multi-token decode scan) plus the
    ``make_state`` factory.  ALL layout/family dispatch lives here, in
    ``layout_for`` / ``resolve_kv_layout`` / ``dense_side``.

Contracts pinned by ``repro-lint`` (``scripts/repro_lint.py``): every
``SequenceState``/``SpecOps`` implementor must define the required
surface with matching arity (rule R4); the per-tick methods marked
``@hot_path`` (``PagedKV.flush`` / ``prepare_tick``, the ``Lane`` decode
scan) must stay free of host syncs (rule R1); and the jitted scan must
keep its step count static so steady-state decode never retraces (rule
R2, asserted at runtime by the ``compile_stability`` bench arm).
"""
from __future__ import annotations

import functools
import hashlib
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hot_path
from repro.core.paged_cache import (BlockPool, ShardedBlockPool, blocks_for,
                                    copy_pool_blocks, prompt_cache_to_blocks,
                                    read_pool_blocks, write_pool_blocks)
from repro.core.uncertainty import get_batched_estimator
from repro.launch.sharding import (cache_shardings, kv_shard_ways,
                                   paged_cache_shardings)


# ---------------------------------------------------------------- slot utils
def stack_slot_caches(model, batch: int, slot_len: int):
    """Zero-initialized stacked per-slot caches: each leaf of the model's
    single-sequence cache gains a leading slot axis."""
    one = model.init_cache(1, slot_len)
    return jax.tree.map(lambda x: jnp.zeros((batch,) + x.shape, x.dtype), one)


def write_slots(slots, bs: List[int], caches: List):
    """Overwrite slots ``bs`` with freshly prefilled single-sequence caches
    in ONE scatter per leaf (k separate ``.at[b].set`` writes would copy the
    whole stacked cache k times).  Also wipes any garbage a retired occupant
    decoded past its budget."""
    idx = jnp.asarray(bs, jnp.int32)
    return jax.tree.map(
        lambda big, *smalls: big.at[idx].set(jnp.stack(smalls)),
        slots, *caches)


def write_slot(slots, b: int, cache):
    """Single-slot convenience wrapper over ``write_slots``."""
    return write_slots(slots, [b], [cache])


def pow2_steps(n: int, cap: int) -> int:
    """Round a residual step count up to a power of two (capped): the decode
    scan is jit-compiled per static ``n_steps``, so bucketing keeps the
    compile set at O(log cap) while the active mask absorbs the overshoot."""
    p = 1
    while p < n:
        p *= 2
    return min(p, cap)


# ---------------------------------------------------------------- layouts
def layout_for(model, kv_layout: str) -> str:
    """Effective per-model layout under the engine-level ``kv_layout``:
    "paged" where the engine runs paged and the family supports it,
    "recurrent" for state-cache families, else "dense"."""
    if kv_layout == "paged" and model.paged_kv:
        return "paged"
    if not model.rewindable_cache:
        return "recurrent"
    return "dense"


def resolve_kv_layout(edge_model, cloud_model, kv_layout: str) -> str:
    """Resolve the engine-level KV layout ("auto" -> paged where BOTH
    models' families page); validates explicit requests."""
    if kv_layout not in ("auto", "paged", "dense"):
        raise ValueError(f"unknown kv_layout {kv_layout!r}; "
                         "known: auto | paged | dense")
    paged_ok = edge_model.paged_kv and cloud_model.paged_kv
    if kv_layout == "paged" and not paged_ok:
        raise ValueError(
            "kv_layout='paged' needs KV-cache transformer families on "
            f"both models, got {edge_model.cfg.family!r} / "
            f"{cloud_model.cfg.family!r}")
    if kv_layout == "auto":
        return "paged" if paged_ok else "dense"
    return kv_layout


# ---------------------------------------------------------------- spec ops
class SpecOps:
    """Traceable per-(model, layout) ops for batched speculative decoding.

    ``step``/``extend`` run one decode step / a multi-token extend over the
    whole group; ``snapshot``/``commit`` implement the per-round rewind.
    Every method is safe to call inside ``jax.jit``.
    """

    def __init__(self, model, layout: str):
        self.model = model
        self.layout = layout
        # speculation commits by a ``pos`` write (KV layouts) or by replay
        # (the recurrent layout); a paged cache with recurrent state rows
        # can do neither
        self.rewindable = layout == "recurrent" or model.rewindable_cache
        if layout == "paged":
            self._step = lambda p, t, c: model.paged_decode_step(p, t[:, :, 0], c)
            self._extend = model.paged_extend_step
        else:
            vstep = jax.vmap(lambda p, t, c: model.decode_step(p, t, c),
                             in_axes=(None, 0, 0))
            vext = jax.vmap(lambda p, t, c: model.extend_step(p, t, c),
                            in_axes=(None, 0, 0))
            self._step = lambda p, t, c: _squeeze1(vstep(p, t, c))
            self._extend = lambda p, t, c: _squeeze1(vext(p, t[:, None, :], c))
        if layout == "recurrent":
            self._vreplay = jax.vmap(
                lambda p, t, c, n: model.replay_step(p, t[None, :], c, n),
                in_axes=(None, 0, 0, 0))
        # token trees need a customizable intra-block mask: dense-layout
        # attention families only (paged extends and recurrent scans are
        # linear-order — see DESIGN.md §Arch-applicability)
        self.tree_ok = (layout == "dense"
                        and model.cfg.family in ("dense", "moe", "vlm"))
        if self.tree_ok:
            self._vext_tree = jax.vmap(
                lambda p, t, c, m, d: model.extend_step(
                    p, t, c, block_mask=m, q_positions=c["pos"] + d),
                in_axes=(None, 0, 0, None, None))

    def step(self, params, tok, caches):
        """tok (G, 1, 1) -> (logits (G, V), caches)."""
        return self._step(params, tok, caches)

    def extend(self, params, tokens, caches):
        """tokens (G, T) -> (logits (G, T, V), caches)."""
        return self._extend(params, tokens, caches)

    def extend_tree(self, params, tokens, caches, block_mask, depths):
        """Tree-masked extend: each slot's ``tokens`` (G, T) row is a packed
        token tree whose node ``i`` attends the cache prefix plus
        ``block_mask[i]`` of the block itself, with RoPE positions
        ``pos + depths``.  Dense attention layouts only (``tree_ok``)."""
        if not self.tree_ok:
            raise ValueError(
                f"token trees need a dense-layout attention model; got "
                f"family {self.model.cfg.family!r} on layout {self.layout!r}")
        logits, caches = self._vext_tree(params, tokens[:, None, :], caches,
                                         block_mask, depths)
        return logits[:, 0], caches

    def reset(self, caches, snap):
        """Roll the group back to the pre-round snapshot WITHOUT committing
        anything (tree rounds re-anchor between draft levels and before the
        replay commit)."""
        if self.layout == "recurrent":
            return snap
        return {**caches, "pos": snap}

    def commit_replay(self, params, caches, snap, tokens, counts):
        """Replay-based commit for tree rounds: the accepted root path's
        K/V live at non-contiguous tree positions, so a bare ``pos`` write
        (``commit``) would keep sibling garbage inside the visible prefix.
        Rewind to the snapshot, re-extend through the padded accepted tape
        ``tokens`` (G, T), then mask to each slot's ``counts`` — one extra
        target pass per round, exactly the seed ``TreeSpecDecoder`` rewind.
        Recurrent layouts already commit by replay."""
        if self.layout == "recurrent":
            return self._vreplay(params, tokens, snap, counts)
        caches = {**caches, "pos": snap}
        _, caches = self.extend(params, tokens, caches)
        return {**caches, "pos": snap + counts}

    def commit_permute(self, caches, snap, perm, counts):
        """Gather-based tree commit for KV layouts: the verify extend
        wrote every tree node's K/V at cache row ``snap + node`` with RoPE
        position ``snap + depth(node)``, and the accepted root path has
        exactly one node per depth — so its rows are already
        position-correct and merely sit at the wrong cache index.  Gather
        them down to the contiguous prefix [snap, snap + T) and advance
        ``pos``: no replay forward pass.  ``perm`` (G, T) holds the path's
        node indices per slot (entries past ``counts`` land beyond ``pos``
        and are dead).  Tree-capable families share the transformer cache
        layout (``k``/``v`` with the sequence on axis -3); recurrent tree
        groups cannot exist (``tree_ok``)."""
        def one(cache, s, pm):
            def move(x):
                rows = jnp.take(x, s + pm, axis=-3, mode="clip")
                return jax.lax.dynamic_update_slice_in_dim(x, rows, s,
                                                           axis=-3)
            return {**cache, "k": move(cache["k"]), "v": move(cache["v"])}

        caches = jax.vmap(one)(caches, snap, perm)
        return {**caches, "pos": snap + counts}

    def snapshot(self, caches):
        """Pre-round rewind anchor: ``pos`` (G,) for KV layouts, the cache
        pytree itself (a device reference, no copy) for recurrent state."""
        if self.layout == "recurrent":
            return caches
        return caches["pos"]

    def commit(self, params, caches, snap, tokens, counts):
        """Rewind the post-round ``caches`` to each slot's accepted prefix:
        ``tokens`` (G, T) is the round's draft tape [pending, d_0..], and
        ``counts`` (G,) int32 (0 for frozen slots) how many of its entries
        each slot commits.  KV: one ``pos`` write (rejected entries stay,
        masked and overwritten).  Recurrent: vmapped ``replay_step`` from
        the snapshot — each slot re-advances through its own prefix in one
        fused scan."""
        if self.layout == "recurrent":
            return self._vreplay(params, tokens, snap, counts)
        return {**caches, "pos": snap + counts}


def _squeeze1(out):
    logits, caches = out
    return logits[:, 0], caches


# ---------------------------------------------------------------- states
class SequenceState:
    """Adapter protocol for the scheduler's per-slot decode state (see the
    module docstring).  ``caches`` is the device pytree the lane's jitted
    step/scan functions consume; everything else is host bookkeeping."""

    layout = "dense"
    caches: Any

    def admit(self, b: int, prompt, need_tokens: int) -> bool:
        """Stage slot ``b``'s prompt prefill; reserve worst-case capacity
        (``need_tokens`` cache entries).  False = defer (capacity full)."""
        raise NotImplementedError

    def begin(self, b: int, prompt, need_tokens: int) -> bool:
        """Reserve capacity for a CHUNKED prefill of slot ``b`` without
        staging any writes — the prompt's cache is built detached (one
        ``Lane.advance_prefill`` chunk per tick) and lands via
        ``finalize``.  Same return contract as ``admit``; layouts without
        reservations accept unconditionally.  Until ``finalize``, the
        slot's device row must stay inert (zero budget masks its decode;
        paged layouts keep the trap row), and the slot must not be picked
        as a preemption victim."""
        return True

    def finalize(self, b: int, cache):
        """Land a finished detached prefill cache into slot ``b`` (staged;
        ``flush`` batches the device writes as for ``admit``)."""
        raise NotImplementedError

    def advance_prefill(self, b: int, params, job: dict) -> bool:
        """Advance slot ``b``'s chunked prefill job (``Lane.start_prefill``)
        one chunk into its detached cache; True when every entry is in.
        Layouts with rows of recurrent state advance the slot's own row."""
        return self.lane.advance_prefill(params, job)

    def detached_len(self, entry_count: int) -> int:
        """Padded length of a detached chunked-prefill cache for a prompt
        with ``entry_count`` entries (layout-dependent: dense slots pad to
        the common slot length, paged to the prompt's own blocks)."""
        raise NotImplementedError

    def share_hints(self, prompts: List[Any]) -> List[bool]:
        """For each prompt in an admission wave: True when admitting it
        MONOLITHICALLY (``admit``) would likely share cache with live or
        same-wave state, so the scheduler should skip chunked prefill for
        it.  A chunked ``begin`` keeps the prompt out of the prefix index
        until ``finalize`` (its blocks hold garbage until then), which
        would silently forfeit sharing between same-wave twins.  Layouts
        without cross-request sharing never prefer the monolithic path."""
        return [False] * len(prompts)

    def flush(self):
        """Land all staged admissions/retirements in batched device writes."""

    def prepare_tick(self, occupied, steps_h, n: int):
        """Grow capacity to cover this tick's real decode steps."""

    def advance(self, n: int):
        """The tick program ran ``n`` steps, each moving every slot's
        ``pos`` on by one (layouts that mirror ``pos`` on the host)."""

    def attn_blocks(self, n: int, actives) -> Dict[str, int]:
        """Counts of the paged decode kernel's walk over the tick that
        ``advance(n)`` just mirrored; none on layouts without a table."""
        return {}

    @property
    def state_rows(self) -> int:
        """Slot rows of recurrent state that each step of the tick program
        reads and writes (0 on layouts that page everything)."""
        return 0

    @property
    def state_row_bytes(self) -> int:
        """Bytes of one slot's row of recurrent state, which a finished
        prefill commits to the tick (0 on layouts that page everything)."""
        return 0

    def retire(self, b: int):
        """Release slot ``b``'s capacity."""

    def fits_empty(self, need_tokens: int, prompt=None) -> bool:
        """True if a request reserving ``need_tokens`` cache entries could
        EVER be admitted (fits an otherwise-empty pool, or its live
        shareable prefix covers the overshoot).  Dense layouts always fit;
        the scheduler uses False to fail fast instead of preempting the
        whole batch for a hopeless request."""
        return True

    def swappable(self, b: int) -> bool:
        """True if slot ``b`` may be chosen as a preemption victim (its
        ``swap_in`` restore is guaranteed to fit the pool eventually)."""
        return False

    def owned_blocks(self, b: int) -> int:
        """KV blocks slot ``b`` currently owns (0 on layouts without a
        block pool) — the preemption cost model's swap-cost proxy, kept
        on the protocol so the scheduler never probes pool internals
        (rule R4)."""
        return 0

    def swap_out(self, b: int):
        """Stage slot ``b``'s cache content to host memory and release its
        device capacity; returns an opaque handle for ``swap_in``.  Only
        meaningful on layouts whose admission can fail (paged)."""
        raise NotImplementedError(f"{type(self).__name__} does not swap")

    def swap_in(self, b: int, handle) -> bool:
        """Restore a swapped-out cache into slot ``b``; False if the pool
        cannot back it yet (the scheduler retries next tick)."""
        raise NotImplementedError(f"{type(self).__name__} does not swap")

    def rebind(self, params):
        """Point future prefills (``admit``/``begin``) at hot-swapped
        ``params``.  An online-adaptation swap is a pure pytree swap —
        same treedef, shapes and dtypes — so caches already staged stay
        valid: decode just reads the new weights the scheduler passes to
        the lane's jitted step.  Kept on the protocol so the scheduler
        never reaches into state internals (rule R4)."""
        self.params = params

    @property
    def capacity_bytes(self) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(self.caches))

    @property
    def peak_bytes(self) -> int:
        return self.capacity_bytes

    def stats(self) -> dict:
        return {}


class DenseKV(SequenceState):
    """Dense stacked slot caches: every slot padded to a common
    ``slot_len`` (the original layout, kept as the parity oracle)."""

    layout = "dense"

    def __init__(self, lane: "Lane", params, batch: int, slot_len: int):
        self.lane = lane
        self.params = params
        self.slot_len = slot_len
        self.caches = stack_slot_caches(lane.model, batch, slot_len)
        self._pend_bs: List[int] = []
        self._pend_caches: List[Any] = []

    def admit(self, b: int, prompt, need_tokens: int) -> bool:
        _, c1 = self.lane.prefill(self.params, prompt, self.slot_len)
        self._pend_bs.append(b)
        self._pend_caches.append(c1)
        return True

    def begin(self, b: int, prompt, need_tokens: int) -> bool:
        return True     # dense slots are pre-reserved; nothing to stage

    def finalize(self, b: int, cache):
        # the whole-slot scatter overwrites whatever masked garbage the
        # slot decoded while the detached prefill was in flight
        self._pend_bs.append(b)
        self._pend_caches.append(cache)

    def detached_len(self, entry_count: int) -> int:
        return self.slot_len

    def flush(self):
        if self._pend_bs:   # one scatter for the whole admission wave
            self.caches = write_slots(self.caches, self._pend_bs,
                                      self._pend_caches)
            self._pend_bs, self._pend_caches = [], []


class RecurrentState(DenseKV):
    """Fixed-size recurrent state (ssm / xlstm / hybrid): stacked like the
    dense layout — recurrent state has no sequence axis to page, so slots
    are O(1)-sized regardless of ``slot_len`` (hybrid's shared-attention
    K/V slabs are the exception and do pad to ``slot_len``).  Differs from
    ``DenseKV`` only in rewind semantics, which live in ``SpecOps``."""

    layout = "recurrent"


class PagedKV(SequenceState):
    """Paged slot caches: one shared block pool + per-slot block tables.

    Host side this owns a ``BlockPool`` (block ids only) and mirrors each
    slot's real content length; device side it owns the cache pytree
    ``{k, v, table, pos}``.  Writes are batched: admissions/retirements
    accumulate and land in ``flush`` (block scatters + ONE table-row/pos
    scatter), per-tick growth lands in ``prepare_tick`` (one table-entry
    scatter).  Retired slots' rows are redirected to the trap block so
    their masked garbage decode cannot corrupt re-allocated blocks.

    PREFIX SHARING: admission consults a host-side prefix-block index
    (prompt-entry bytes -> live block ids).  A new request whose prompt
    shares a block-aligned prefix — or is an exact twin — of an in-flight
    slot's prompt maps those blocks into its own table via refcount bumps
    (``share_prefix``) instead of re-allocating and re-prefilling them;
    causal attention makes prefix K/V bit-identical across prompts, so
    token parity with the dense oracle is exact.  The first divergent
    decode write into a shared block forks a private copy first
    (``cow_split`` — copy-on-write), and index entries are invalidated the
    moment their backing block dies or is mutated.

    SWAP: ``swap_out`` stages a slot's blocks to host memory
    (``jax.device_get``) and releases them; ``swap_in`` restores the
    content bit-for-bit, so a preempted request resumes mid-decode with
    identical tokens.  On restore, ``swap_in`` RE-CONSULTS the prefix-block
    index: the full blocks of the victim's prompt that are still live (a
    resident twin, a shared system prefix) are re-shared via refcount bumps
    instead of paying private copies — only the tail past the indexed
    prefix is re-allocated and re-written.  Shared full prompt blocks are
    never decode-written by the resumed slot (its write frontier sits past
    the prompt), so no CoW reservation is needed on restore.
    """

    layout = "paged"

    def __init__(self, lane: "Lane", params, batch: int, slot_len: int,
                 block_size: int, num_blocks: Optional[int] = None, *,
                 data_shards: int = 1, kv_ways: int = 1):
        self.lane = lane
        self.params = params
        self.block_size = block_size
        self.max_blocks = blocks_for(slot_len, block_size)
        self.data_shards = data_shards
        self.kv_ways = kv_ways
        if data_shards > 1 and batch % data_shards != 0:
            raise ValueError(f"batch {batch} does not divide into "
                             f"{data_shards} data shards")
        self._spb = batch // max(data_shards, 1)    # slots per shard
        # sharded pools keep the SINGLE-DEVICE default's per-device byte
        # budget: each block's bytes divide kv_ways ways over 'model' and
        # the block dim data_shards ways over the data axes, so total
        # capacity scales with kv_shards = data_shards * kv_ways at the
        # same per-device HBM — the point of sharding the pool
        if data_shards > 1:
            if num_blocks is None:
                per_shard = (batch * self.max_blocks + 1) * kv_ways
            else:                   # explicit num_blocks = TOTAL blocks
                per_shard = -(-num_blocks // data_shards)
            per_shard = max(per_shard, 2)
            num_blocks = data_shards * per_shard
            self.pool = ShardedBlockPool(data_shards, per_shard,
                                         block_size, self._shard_of)
        else:
            if num_blocks is None:  # worst-case-safe default: dense capacity
                num_blocks = (batch * self.max_blocks + 1) * kv_ways
            num_blocks = max(num_blocks, 2)
            self.pool = BlockPool(num_blocks, block_size)
        self.caches = lane.model.init_paged_cache(
            num_blocks, block_size, batch, self.max_blocks)
        self._block_bytes = (self.caches["k"].nbytes +
                             self.caches["v"].nbytes) // num_blocks
        self._len = [0] * batch     # real cache entries written per slot
        # host mirror of the device's caches["pos"]: set where flush sets
        # it, moved on by advance; no device pull
        self.pos_h = np.zeros((batch,), np.int64)
        self._commit = [0] * batch  # blocks reserved for future growth
        self._entries: List[Optional[np.ndarray]] = [None] * batch  # prompts
        self._stale: set = set()    # retired slots awaiting a trap row
        self._pend: List[Tuple[int, np.ndarray, int]] = []  # (b, row, pos)
        # prefix-block index: prompt-entry bytes -> block ids holding them
        self._prefix_index: Dict[bytes, Tuple[int, ...]] = {}
        self._indexed: set = set()  # blocks referenced by any index entry
        # CoW reservations: shared tail block -> slots that reserved one
        # future fork block for it (their _commit carries the headroom)
        self._cow_rsv: Dict[int, List[int]] = {}
        self._prefix_hits = 0       # admissions that shared >= 1 block
        self._shared_blocks = 0     # physical allocations avoided
        self._cow_forks = 0
        self._swaps = 0
        # chunked prefills in flight: slot -> (entries, new blocks, shared)
        self._begun: Dict[int, Tuple[np.ndarray, List[int], int]] = {}

    # ------------------------------------------------------------ shards
    def _shard_of(self, b: int) -> int:
        """Data shard owning slot ``b`` (contiguous slot groups; 0 when the
        pool is unsharded)."""
        return b // self._spb if self.data_shards > 1 else 0

    def _pkey(self, shard: int, key: bytes):
        """Prefix-index key: the digest alone on the single pool; scoped by
        shard on sharded pools — prefix sharing/CoW stay host-side
        PER-SHARD, a slot can only map blocks its own shard owns."""
        return key if self.data_shards <= 1 else (shard, key)

    def _commit_sum(self, b: int) -> int:
        """Outstanding growth reservations charged against slot ``b``'s
        shard (all slots on the single pool)."""
        if self.data_shards <= 1:
            return sum(self._commit)
        s = self._shard_of(b)
        return sum(self._commit[s * self._spb:(s + 1) * self._spb])

    # ------------------------------------------------------------ prefix
    def _prefix_keys(self, entries: np.ndarray) -> List[bytes]:
        """Chained per-block digests: ``key[j]`` identifies the token
        prefix covering blocks 0..j (``min((j+1)*bs, E)`` entries), as
        ``blake2b(key[j-1] || block_j_bytes)``.  One O(E) pass yields
        every prefix key as a 16-byte digest — raw prefix byte-strings as
        keys would cost O(E^2/bs) hashing and index memory per prompt,
        quadratic on the admission path for long prompts."""
        E, bs = entries.size, self.block_size
        keys, prev = [], b""
        for j in range(blocks_for(E, bs)):
            prev = hashlib.blake2b(
                prev + entries[j * bs:min((j + 1) * bs, E)].tobytes(),
                digest_size=16).digest()
            keys.append(prev)
        return keys

    def _lookup_prefix(self, entries: np.ndarray,
                       shard: int = 0) -> Tuple[int, List[int]]:
        """Longest indexed prefix of ``entries`` within ``shard``: the
        exact entry count first (twin — shares the partial tail block
        too), then block-aligned lengths descending.  Returns (entries
        matched, block ids)."""
        E, bs = entries.size, self.block_size
        keys = self._prefix_keys(entries)
        for j in range(len(keys) - 1, -1, -1):
            got = self._prefix_index.get(self._pkey(shard, keys[j]))
            if got is not None:
                return min((j + 1) * bs, E), list(got)
        return 0, []

    def _register(self, entries: np.ndarray, blocks: List[int],
                  shard: int = 0):
        """Index every block-aligned prefix of ``entries`` (plus the full
        partial-tail prefix) under the blocks that hold it.  First
        registrant wins — twins share the original's blocks."""
        for j, key in enumerate(self._prefix_keys(entries)):
            self._prefix_index.setdefault(self._pkey(shard, key),
                                          tuple(blocks[:j + 1]))
        self._indexed.update(blocks)

    def _reindex(self):
        self._indexed = {blk for v in self._prefix_index.values()
                         for blk in v}

    def _purge_blocks(self, dead):
        """Drop index entries backed by any block that died."""
        dd = set(dead) & self._indexed
        if dd:
            self._prefix_index = {k: v for k, v in self._prefix_index.items()
                                  if not dd.intersection(v)}
            self._reindex()

    def _purge_written(self, blk: int):
        """Drop index entries referencing ``blk`` — its content is about
        to be mutated by a decode write.  O(1) when the block is not
        indexed (the steady state after the first write)."""
        if blk in self._indexed:
            self._prefix_index = {k: v for k, v in self._prefix_index.items()
                                  if blk not in v}
            self._reindex()

    def share_prefix(self, b: int, entries: np.ndarray,
                     _peek: Optional[Tuple[int, List[int]]] = None) -> int:
        """Map the longest indexed prefix of ``entries`` into slot ``b``
        (refcount bumps, no allocation), registering a CoW reservation
        when the shared tail is partial (slot ``b``'s ``_commit`` must
        already carry that one-block headroom).  Returns the number of
        cache entries covered (0 = no match; caller prefills everything).
        ``_peek`` lets ``admit`` reuse its sizing lookup instead of
        re-hashing every prefix slice."""
        m, shared = _peek if _peek is not None else \
            self._lookup_prefix(entries, self._shard_of(b))
        if shared:
            self.pool.share(b, shared)
            if m % self.block_size:
                self._cow_rsv.setdefault(shared[-1], []).append(b)
            self._prefix_hits += 1
            self._shared_blocks += len(shared)
        return m

    def _drop_cow_rsv(self, b: int) -> int:
        """Remove slot ``b``'s outstanding CoW reservations (its commit
        headroom leaves with it); returns how many were dropped."""
        n = 0
        for blk in list(self._cow_rsv):
            lst = self._cow_rsv[blk]
            while b in lst:
                lst.remove(b)
                n += 1
            if not lst:
                del self._cow_rsv[blk]
        return n

    def cow_split(self, b: int):
        """Make slot ``b``'s next decode-write target block private.

        The only pre-existing block a decode write can land in is the
        partial tail block at ``_len // block_size`` (growth allocates the
        rest fresh).  If it is shared (refcount > 1) fork a private copy —
        copy-on-write at first divergence; if it is exclusively owned,
        just invalidate any index entries over its (about to change)
        content.  Returns (src, dst, table_index) for the staged device
        copy, or None.

        The fork block is drawn from a SHARER's reservation, not
        necessarily the forking slot's: a tail shared by k sharers forks
        exactly k-1 times (the last writer keeps the original in place),
        and it is the k sharers — never the original registrant — whose
        admissions reserved the headroom.  Whichever slot forks first
        consumes one of those reservations, keeping ``free >=
        sum(_commit)`` exact however retire/preempt interleave."""
        E, bs = self._len[b], self.block_size
        if E % bs == 0:
            return None             # next write opens a fresh block
        i0 = E // bs
        blk = self.pool.owned(b)[i0]
        if self.pool.refcount(blk) > 1:
            new = self.pool.fork(b, blk)
            rsv = self._cow_rsv.get(blk)
            if rsv:
                s = rsv.pop()
                self._commit[s] = max(self._commit[s] - 1, 0)
                if not rsv:
                    del self._cow_rsv[blk]
            self._cow_forks += 1
            return blk, new, i0
        self._purge_written(blk)
        return None

    # ------------------------------------------------------------ admit
    def admit(self, b: int, prompt, need_tokens: int) -> bool:
        """Allocate the prompt's blocks and stage the prefill; returns
        False (admission deferred/preempted) when the pool cannot back the
        request.

        Admission is reservation-based: the request's WORST-CASE block need
        (``need_tokens`` = prompt + budget [+ overdraft], plus one block if
        a shared partial tail will need a copy-on-write fork) is committed
        up front so on-demand growth can never fail mid-flight, but blocks
        are only physically allocated as decode reaches them — the
        reservation is per-request, not the batch maximum, which is where
        the paged layout beats the dense slabs.  Shared prefix blocks
        count against nobody's reservation: they are live already."""
        prompt = np.asarray(prompt, np.int32)
        entries = prompt[:-1]
        got = self._reserve(b, entries, need_tokens)
        if got is None:
            return False
        ns, blocks = got
        if blocks:                  # prefill; write only the unshared tail
            nb = self.pool.blocks_for(entries.size)
            _, c1 = self.lane.prefill(self.params, prompt,
                                      nb * self.block_size)
            self._land(b, entries, blocks, ns, c1)
        else:
            self._land(b, entries, blocks, ns, None)
        return True

    def _reserve(self, b: int, entries: np.ndarray,
                 need_tokens: int) -> Optional[Tuple[int, List[int]]]:
        """Shared half of ``admit``/``begin``: map the live shared prefix,
        allocate the prompt's own blocks, commit worst-case growth.
        Returns (shared block count, newly allocated block ids), or None
        when the pool cannot back the request (nothing mutated)."""
        E = entries.size
        nb = self.pool.blocks_for(E)
        total = self.pool.blocks_for(need_tokens)
        m, shared = self._lookup_prefix(entries,        # sizing peek
                                        self._shard_of(b))
        own_new = nb - len(shared)
        cow_extra = 1 if shared and (m % self.block_size) else 0
        if not self.pool.can_alloc(own_new + (total - nb) + cow_extra
                                   + self._commit_sum(b), owner=b):
            return None
        ns = 0
        if shared:
            self.share_prefix(b, entries, _peek=(m, shared))
            ns = len(shared)
        blocks = self.pool.alloc(b, own_new) if own_new else []
        self._commit[b] = (total - nb) + cow_extra
        return ns, blocks

    def _land(self, b: int, entries: np.ndarray, blocks: List[int],
              ns: int, c1) -> None:
        """Stage a fully prefilled prompt into slot ``b``'s table row and
        the prefix index (``c1``: the prompt's single-sequence cache, or
        None when every block was shared)."""
        E = entries.size
        if blocks:
            self._write_blocks(b, E, blocks, ns, c1)
        mine = self.pool.owned(b)
        # pad = trap block (the slot's shard's trap on sharded pools)
        row = np.full((self.max_blocks,), self.pool.trap(b), np.int32)
        row[:len(mine)] = mine
        self._pend.append((b, row, E))
        self._len[b] = E
        self._entries[b] = entries
        self._stale.discard(b)
        self._register(entries, mine, self._shard_of(b))

    def _write_blocks(self, b: int, E: int, blocks: List[int], ns: int,
                      c1) -> None:
        """Write a prefilled prompt's K/V (``E`` entries) into the blocks
        allocated for it, those after its ``ns`` shared ones."""
        nb = self.pool.blocks_for(E)
        kb, vb = prompt_cache_to_blocks(
            {"k": c1["k"][:, :, :nb * self.block_size],
             "v": c1["v"][:, :, :nb * self.block_size]},
            self.block_size)
        self.caches["k"], self.caches["v"] = write_pool_blocks(
            self.caches["k"], self.caches["v"],
            jnp.asarray(blocks, jnp.int32), kb[:, ns:], vb[:, ns:])

    def begin(self, b: int, prompt, need_tokens: int) -> bool:
        """Reserve blocks for a chunked prefill; the slot's device row
        stays a TRAP row until ``finalize`` (it decodes masked garbage
        while the detached prefill runs), and ``_register`` waits too —
        the reserved blocks hold garbage until the finalize write."""
        entries = np.asarray(prompt, np.int32)[:-1]
        got = self._reserve(b, entries, need_tokens)
        if got is None:
            return False
        ns, blocks = got
        self._begun[b] = (entries, blocks, ns)
        return True

    def finalize(self, b: int, cache):
        entries, blocks, ns = self._begun.pop(b)
        self._land(b, entries, blocks, ns, cache)

    def detached_len(self, entry_count: int) -> int:
        return self.pool.blocks_for(entry_count) * self.block_size

    def share_hints(self, prompts: List[Any]) -> List[bool]:
        """A prompt prefers the monolithic path when its first-block
        prefix key is already live in the index, or at least one other
        prompt in the same wave opens with the same block (the pair would
        have shared had the leader landed first).  Only the first block's
        key is probed — the cheapest sound signal: any shared prefix at
        all implies a shared first block."""
        firsts: List[Optional[bytes]] = []
        for p in prompts:
            entries = np.asarray(p, np.int32)[:-1]
            if entries.size == 0:
                firsts.append(None)
                continue
            firsts.append(hashlib.blake2b(
                entries[:self.block_size].tobytes(),
                digest_size=16).digest())
        counts = Counter(k for k in firsts if k is not None)
        # slot (and so shard) assignment happens after the hint, so probe
        # every shard's index — a miss only costs a chunking opportunity
        shards = range(max(self.data_shards, 1))
        return [k is not None
                and (any(self._pkey(s, k) in self._prefix_index
                         for s in shards) or counts[k] > 1)
                for k in firsts]

    def fits_empty(self, need_tokens: int, prompt=None) -> bool:
        total = self.pool.blocks_for(need_tokens)
        if total <= self.pool.usable():
            return True
        if prompt is not None:      # admissible via currently-live sharing?
            entries = np.asarray(prompt, np.int32)[:-1]
            for s in range(max(self.data_shards, 1)):
                m, shared = self._lookup_prefix(entries, s)
                cow = 1 if shared and (m % self.block_size) else 0
                if total - len(shared) + cow <= self.pool.usable():
                    return True
        return False

    def swappable(self, b: int) -> bool:
        """A victim is only worth swapping if its restore is GUARANTEED.
        ``swap_in`` does re-share still-indexed full prompt blocks, but
        that is opportunistic — the index entries can die while the victim
        sits on the host (the twin retires, a block is written) — so the
        guarantee must assume the worst case: every logical block restored
        privately.  A slot admitted only thanks to prefix sharing, with a
        private footprint larger than the pool, could otherwise never come
        back."""
        rsv = sum(b in lst for lst in self._cow_rsv.values())
        return (len(self.pool.owned(b)) + self._commit[b] - rsv
                <= self.pool.usable())

    def owned_blocks(self, b: int) -> int:
        return len(self.pool.owned(b))

    @hot_path
    def flush(self):
        if not (self._pend or self._stale):
            return
        idx, rows, poss = [], [], []
        for b, row, p in self._pend:
            idx.append(b)
            rows.append(row)
            poss.append(p)
        for b in self._stale:       # retired, not re-admitted: trap row
            idx.append(b)
            rows.append(np.full((self.max_blocks,), self.pool.trap(b),
                                np.int32))
            poss.append(0)
        ii = jnp.asarray(idx, jnp.int32)
        self.caches["table"] = self.caches["table"].at[ii].set(
            jnp.asarray(np.stack(rows)))
        self.caches["pos"] = self.caches["pos"].at[ii].set(
            jnp.asarray(poss, jnp.int32))
        self.pos_h[idx] = poss
        self._pend, self._stale = [], set()

    def advance(self, n: int):
        self.pos_h += n

    def attn_blocks(self, n: int, actives) -> Dict[str, int]:
        """Over the ``n`` steps: the table entries a walk of every block
        would read (``attn_blocks_table``); the blocks the kernel walks,
        ``min(cdiv(pos + 1, bs), MB)`` per slot and step
        (``attn_blocks_visited``); and those of the slots that emitted in
        that step, ``actives`` (n, B) bool (``attn_blocks_live``)."""
        pos = self.pos_h - n + np.arange(n)[:, None]            # (n, B)
        walk = np.minimum(-(-(pos + 1) // self.block_size),
                          self.max_blocks)
        return {"attn_blocks_table": walk.size * self.max_blocks,
                "attn_blocks_visited": int(walk.sum()),
                "attn_blocks_live": int(walk[actives].sum())}

    @hot_path
    def prepare_tick(self, occupied, steps_h, n: int):
        """Grow every occupied slot to cover this tick's REAL decode steps
        (``min(steps_left, n)``); the masked garbage tail past a slot's
        budget clamps into the trap.  Growth draws down the slot's
        admission-time reservation, so it cannot fail.  Before growing,
        ``cow_split`` forks any shared partial tail block the tick is
        about to write into (one batched device copy for the wave)."""
        upd_b, upd_i, upd_blk = [], [], []
        cow_src, cow_dst = [], []
        for b in occupied:
            steps = min(int(steps_h[b]), n)
            if steps <= 0:
                continue
            cow = self.cow_split(b)
            if cow is not None:
                src, dst, i0 = cow
                cow_src.append(src)
                cow_dst.append(dst)
                upd_b.append(b)
                upd_i.append(i0)
                upd_blk.append(dst)
            target = self._len[b] + steps
            new = self.pool.grow_to(b, target)
            self._commit[b] = max(self._commit[b] - len(new), 0)
            base = len(self.pool.owned(b)) - len(new)
            for j, blk in enumerate(new):
                upd_b.append(b)
                upd_i.append(base + j)
                upd_blk.append(blk)
            self._len[b] = target
        if cow_src:
            self.caches["k"], self.caches["v"] = copy_pool_blocks(
                self.caches["k"], self.caches["v"],
                jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(cow_dst, jnp.int32))
        if upd_b:
            self.caches["table"] = self.caches["table"].at[
                jnp.asarray(upd_b, jnp.int32),
                jnp.asarray(upd_i, jnp.int32)].set(
                jnp.asarray(upd_blk, jnp.int32))

    def retire(self, b: int):
        self._drop_cow_rsv(b)
        self._purge_blocks(self.pool.free(b))
        self._len[b] = 0
        self._commit[b] = 0
        self._entries[b] = None
        self._stale.add(b)

    # ------------------------------------------------------------ swap
    def swap_out(self, b: int) -> dict:
        """Stage slot ``b``'s blocks to host memory and free them.  The
        handle is self-contained (content, entry count, outstanding
        reservation): ``swap_in`` restores it bit-for-bit, so the resumed
        decode emits exactly the tokens the uninterrupted run would.  Any
        unconsumed CoW reservation is shed — the restored copy is fully
        private, so no fork can ever hit it."""
        ids = self.pool.owned(b)
        k, v = read_pool_blocks(self.caches["k"], self.caches["v"],
                                jnp.asarray(ids, jnp.int32))
        commit = max(self._commit[b] - self._drop_cow_rsv(b), 0)
        handle = {"k": jax.device_get(k), "v": jax.device_get(v),
                  "len": self._len[b], "commit": commit,
                  "entries": self._entries[b]}
        self._purge_blocks(self.pool.free(b))
        self._len[b] = 0
        self._commit[b] = 0
        self._entries[b] = None
        self._stale.add(b)
        self._swaps += 1
        return handle

    def swap_in(self, b: int, handle: dict) -> bool:
        """Restore a swapped-out slot into ``b``; False when the pool
        cannot back its blocks + outstanding reservation yet.

        Re-consults the prefix-block index over the victim's prompt: FULL
        prompt blocks still live in the index (a resident twin's, a shared
        system prefix) are mapped back by refcount bump instead of a
        private re-allocation + re-write.  Only full-block matches are
        taken — the restored content past the indexed prefix (partial tail
        block, generated tokens) is private by construction, and the
        resumed slot's write frontier (``len >= prompt entries``) can
        never land in a shared full prompt block, so no CoW reservation is
        needed."""
        nb = handle["k"].shape[1]
        entries = handle.get("entries")
        ns, shared = 0, []
        if entries is not None:
            m, cand = self._lookup_prefix(entries, self._shard_of(b))
            ns = min(m // self.block_size, nb)
            shared = cand[:ns]
        if not self.pool.can_alloc((nb - ns) + handle["commit"]
                                   + self._commit_sum(b), owner=b):
            return False
        if shared:
            self.pool.share(b, shared)
            self._prefix_hits += 1
            self._shared_blocks += ns
        blocks = self.pool.alloc(b, nb - ns) if nb > ns else []
        self._commit[b] = handle["commit"]
        if nb > ns:
            self.caches["k"], self.caches["v"] = write_pool_blocks(
                self.caches["k"], self.caches["v"],
                jnp.asarray(blocks, jnp.int32),
                jnp.asarray(handle["k"][:, ns:]),
                jnp.asarray(handle["v"][:, ns:]))
        mine = self.pool.owned(b)
        row = np.full((self.max_blocks,), self.pool.trap(b), np.int32)
        row[:nb] = mine
        self._pend.append((b, row, handle["len"]))
        self._len[b] = handle["len"]
        self._entries[b] = entries
        self._stale.discard(b)
        if entries is not None:
            # restored PROMPT blocks are index-worthy again (first
            # registrant wins, so a live twin's entries are untouched);
            # generated-token blocks past the prompt stay out of the index
            # so their first write keeps the O(1) purge fast path
            self._register(entries, mine[:blocks_for(entries.size,
                                                     self.block_size)],
                           self._shard_of(b))
        return True

    @property
    def peak_bytes(self) -> int:
        """High-water mark of LIVE block bytes — what a right-sized pool
        would have to hold (the benchmark's headline number).  Shared
        blocks count once: prefix sharing lowers this directly."""
        return self.pool.peak_used * self._block_bytes

    @property
    def capacity_bytes(self) -> int:
        return self.caches["k"].nbytes + self.caches["v"].nbytes

    def stats(self) -> dict:
        # usable capacity: pool minus trap(s) — per-shard traps on sharded
        # pools.  kv_shards is the total byte-division factor (data shards
        # x model-axis kv ways): the per-device footprint of this capacity
        # is capacity_bytes / kv_shards
        if self.data_shards > 1:
            cap = self.data_shards * (self.pool.per_shard - 1)
        else:
            cap = self.pool.num_blocks - 1
        return {"kv_blocks_peak": self.pool.peak_used,
                "kv_block_size": self.block_size,
                "kv_prefix_hits": self._prefix_hits,
                "kv_shared_blocks": self._shared_blocks,
                "kv_cow_forks": self._cow_forks,
                "kv_swaps": self._swaps,
                "kv_shards": self.data_shards * self.kv_ways,
                "kv_capacity_blocks": cap}


@functools.partial(jax.jit, donate_argnums=0)
def commit_state_row(rows, one, b):
    """Write one sequence's recurrent state ``one`` (leaves ``(L, 1,
    ...)``) into slot ``b`` of the stacked rows (leaves ``(L, B, ...)``),
    in place: one program whatever the slot."""
    return jax.tree.map(
        lambda big, x: jax.lax.dynamic_update_index_in_dim(
            big, x[:, 0].astype(big.dtype), b, 1), rows, one)


class PagedState(PagedKV):
    """``PagedKV`` for a model's attention layers beside one row of
    recurrent state per slot (``Model.recurrent_rows``: granitehybrid's
    Mamba2 layers) in the same cache pytree (``caches["ssm"]``), so one
    adapter owns both kinds of state.

    A chunked prefill advances the slot's own row in place
    (``Lane.advance_prefill_row``; its first chunk starts the row afresh)
    beside a detached cache of the attention layers' K/V, which
    ``finalize`` lands in the slot's blocks; a whole-prompt admission
    lands its K/V blocks and its state row (``commit_state_row``).  The
    tick program advances the rows of decoding slots in place (its lane
    donates the cache) and leaves the others alone.  A retired slot's row
    holds its last state until the next occupant's prefill starts it
    afresh.  Every prompt's detached K/V has the slot's full length, so
    one chunk program and one landing serve every prompt.

    Not yet: prefix sharing (shared blocks carry no state at the prefix's
    end, so every prompt prefills whole) and swap (rows are not staged to
    the host, so no slot is a preemption victim: a full pool defers
    admission instead)."""

    def __init__(self, lane: "Lane", params, batch: int, slot_len: int,
                 block_size: int, num_blocks: Optional[int] = None, *,
                 data_shards: int = 1, kv_ways: int = 1):
        if data_shards > 1 or kv_ways > 1:
            raise ValueError("recurrent state rows are not sharded; serve "
                             f"family {lane.model.cfg.family!r} off the mesh")
        super().__init__(lane, params, batch, slot_len, block_size,
                         num_blocks)
        self._rows = batch
        self._row_bytes = sum(x.nbytes for x in
                              jax.tree.leaves(self.caches["ssm"])) // batch

    @property
    def state_rows(self) -> int:
        return self._rows

    @property
    def state_row_bytes(self) -> int:
        return self._row_bytes

    @property
    def capacity_bytes(self) -> int:
        return super().capacity_bytes + self._rows * self._row_bytes

    def detached_len(self, entry_count: int) -> int:
        return self.max_blocks * self.block_size

    def advance_prefill(self, b: int, params, job: dict) -> bool:
        finished, self.caches["ssm"] = self.lane.advance_prefill_row(
            params, job, self.caches["ssm"], b)
        return finished

    def _write_blocks(self, b: int, E: int, blocks: List[int], ns: int,
                      c1) -> None:
        """Every block of the prefilled cache, those past the prompt's own
        into the trap (one program for every prompt of a detached
        prefill, whose cache has the slot's full length), then, for a
        whole-prompt prefill, the slot's state row (a chunked one is in
        the row already)."""
        kb, vb = prompt_cache_to_blocks(c1, self.block_size)
        ids = np.full((kb.shape[1],), self.pool.trap(b), np.int32)
        ids[:len(blocks)] = blocks
        self.caches["k"], self.caches["v"] = write_pool_blocks(
            self.caches["k"], self.caches["v"], jnp.asarray(ids), kb, vb)
        if "ssm" in c1:
            self.caches["ssm"] = commit_state_row(self.caches["ssm"],
                                                  c1["ssm"], b)

    def _lookup_prefix(self, entries: np.ndarray,
                       shard: int = 0) -> Tuple[int, List[int]]:
        return 0, []

    def _register(self, entries: np.ndarray, blocks: List[int],
                  shard: int = 0):
        pass

    def share_hints(self, prompts: List[Any]) -> List[bool]:
        return [False] * len(prompts)

    def swappable(self, b: int) -> bool:
        return False


# ---------------------------------------------------------------- lane
class Lane:
    """Jitted batched machinery for ONE model in ONE layout: the batched
    decode step (``SpecOps.step``), a per-prompt-length prefill, the
    multi-token decode scan shared by all layouts, and the ``make_state``
    factory the scheduler calls instead of picking adapters itself."""

    def __init__(self, model, estimator: str, temperature: float,
                 layout: str = "dense", block_size: int = 32,
                 mesh=None, data_shards: int = 1):
        self.model = model
        self.estimator = estimator
        self.temperature = temperature
        self.layout = layout
        self.block_size = block_size
        self.mesh = mesh
        self._dense_side: Optional["Lane"] = None
        self.data_shards = data_shards if mesh is not None else 1
        # model-axis byte division of the paged pool (1 when this model's
        # kv-heads/head-dim don't divide — replication fallback)
        self.kv_ways = kv_shard_ways(mesh, model.cfg) if mesh is not None \
            else 1
        self.ops = SpecOps(model, layout)
        est = get_batched_estimator(estimator)
        step = self.ops.step
        # KV-transformer attention masks every key row past ``pos``
        # (score -> -inf -> exp = 0 exactly), so prefilling a prompt PADDED
        # to a pow2 bucket and then pinning ``pos`` back to the real length
        # is bit-identical to an exact-length prefill — that is what lets
        # admission bucket prompt lengths instead of compiling one prefill
        # per distinct length.  Recurrent families (ssm/xlstm/hybrid)
        # advance state through EVERY input token, pads included, so they
        # must keep exact-length compiles; encdec's cross-attention reads
        # the full encoder output and is excluded for the same reason.
        self._bucket_prefill = layout in ("dense", "paged") and \
            model.cfg.family in ("dense", "moe", "vlm")
        # a paged cache with a row of recurrent state per slot: a chunked
        # prefill advances its slot's row between ticks, every chunk padded
        # to one length with the count of real tokens (one chunk program
        # for every prompt), and the tick leaves the rows of slots that do
        # not decode alone
        rowed = layout == "paged" and model.recurrent_rows
        if rowed:
            def extend_row(p, toks, kv, rows, b, n, first):
                """A padded chunk advanced in slot ``b``'s own row of
                ``rows`` (leaves ``(L, B, ...)``), started afresh on the
                job's ``first`` chunk, beside the job's detached K/V."""
                fresh = model.init_cache(1, 1)["ssm"]
                row = jax.tree.map(
                    lambda x, f: jnp.where(
                        first, f.astype(x.dtype),
                        jax.lax.dynamic_slice_in_dim(x, b, 1, 1)),
                    rows, fresh)
                _, c = model.extend_step(p, toks, {**kv, "ssm": row},
                                         valid=n)
                rows = jax.tree.map(
                    lambda x, r: jax.lax.dynamic_update_slice_in_dim(
                        x, r.astype(x.dtype), b, 1), rows, c.pop("ssm"))
                return c, rows

            self._jit_extend_row = jax.jit(extend_row, donate_argnums=(2, 3))
            self._jit_kv_zeros = jax.jit(
                lambda n: {k: v for k, v in model.init_cache(1, n).items()
                           if k != "ssm"}, static_argnums=0)
        self._jit_prefill = jax.jit(
            lambda p, toks, max_seq: model.prefill(
                p, {"tokens": toks}, max_seq=max_seq),
            static_argnames=("max_seq",))
        self._jit_extend = jax.jit(
            lambda p, toks, cache: model.extend_step(p, toks, cache))

        @hot_path
        def chunk(params, caches, tok, steps_left, unc_sum, rng, stop,
                  n_steps: int, topk: int = 0):
            """n_steps decode steps over all slots in one scan.  Returns the
            advanced state plus per-step (token, active) for the host.
            ``stop`` is a traced int32 stop-token id (-1 = never): a slot
            that emits it keeps the token but zeroes its remaining budget,
            so it retires early with steps-spent < budget.  ``topk > 0``
            (static) additionally emits each step's top-k logit values and
            vocab indices — teacher supervision for serve-time adaptation,
            coming out through the SAME batched pull as the token tape
            (capture never adds a sync); the default-0 path traces the
            exact tuple it always has, byte-identical."""
            def body(carry, r):
                caches, tok, steps_left, unc_sum = carry
                if rowed:
                    # only decoding slots advance their state rows
                    lg, caches = model.paged_decode_step(
                        params, tok[:, :, 0], caches, live=steps_left > 0)
                else:
                    lg, caches = step(params, tok, caches)   # (B, V)
                active = steps_left > 0
                if temperature == 0.0:
                    nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                else:
                    nxt = jax.random.categorical(
                        r, lg / temperature, axis=-1).astype(jnp.int32)
                unc_sum = unc_sum + jnp.where(active, est(lg), 0.0)
                steps_left = jnp.where(active & (nxt == stop),
                                       0, steps_left - active.astype(jnp.int32))
                out = (nxt, active)
                if topk:
                    tv, ti = jax.lax.top_k(lg.astype(jnp.float32), topk)
                    out = (nxt, active, tv, ti.astype(jnp.int32))
                return (caches, nxt[:, None, None], steps_left, unc_sum), out

            carry = (caches, tok, steps_left, unc_sum)
            keys = jax.random.split(rng, n_steps)
            if topk:
                (caches, tok, steps_left, unc_sum), \
                    (toks, actives, tvals, tidx) = \
                    jax.lax.scan(body, carry, keys)
                return (caches, tok, steps_left, unc_sum, toks, actives,
                        tvals, tidx)
            (caches, tok, steps_left, unc_sum), (toks, actives) = \
                jax.lax.scan(body, carry, keys)
            return caches, tok, steps_left, unc_sum, toks, actives

        # a paged cache with a row of recurrent state per slot is advanced
        # in place: a second copy of every slot's state would not fit
        donate = {"donate_argnames": ("caches",)} if rowed else {}
        self._chunk = jax.jit(chunk, static_argnames=("n_steps", "topk"),
                              **donate)

    def dense_side(self) -> "Lane":
        """This lane's model re-hosted on dense per-slot caches (cached
        after the first call).  Tree/self speculation needs block-masked
        extends — a dense-layout feature — so escalation groups build
        their side states through here instead of the scheduler ever
        comparing ``.layout`` (rule R4 keeps layout dispatch out of it).
        Identity on lanes that are already dense."""
        if self.layout == "dense":
            return self
        if self._dense_side is None:
            self._dense_side = Lane(self.model, self.estimator,
                                    self.temperature, layout="dense",
                                    block_size=self.block_size,
                                    mesh=self.mesh,
                                    data_shards=self.data_shards)
        return self._dense_side

    def prefill(self, params, prompt, max_seq: int):
        """Prefill ``prompt[:-1]`` into a fresh cache padded to ``max_seq``.
        KV-transformer lanes pad the ENTRY COUNT to a pow2 bucket (capped
        at ``max_seq``) and pin ``pos`` back to the real length — bit-exact
        (see ``_bucket_prefill``), and it bounds the compile set at
        O(log max prompt) instead of one compile per distinct length.
        Recurrent/encdec lanes still recompile per distinct prompt length."""
        entries = np.asarray(prompt, np.int32)[:-1]
        E = entries.size
        Ep = min(pow2_steps(E, 1 << 30), max_seq) if self._bucket_prefill \
            else E
        if Ep > E:
            entries = np.concatenate([entries, np.zeros(Ep - E, np.int32)])
        lg, cache = self._jit_prefill(params, jnp.asarray(entries[None]),
                                      max_seq=max_seq)
        if Ep > E:
            cache = {**cache, "pos": jnp.full_like(cache["pos"], E)}
        return lg, cache

    # ------------------------------------------------------------ chunked
    def start_prefill(self, params, prompt, max_seq: int, chunk: int) -> dict:
        """Open a CHUNKED prefill job: the prompt's entries are advanced
        ``chunk`` tokens per ``advance_prefill`` call into a DETACHED
        single-sequence cache (padded to ``max_seq``), so a long prompt
        never stalls the in-flight decode batch behind one monolithic
        prefill — the scheduler interleaves one chunk per tick with decode
        and lands the finished cache through ``SequenceState.finalize``."""
        entries = np.asarray(prompt, np.int32)[:-1]
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        return {"entries": entries, "done": 0, "cache": None,
                "max_seq": max_seq, "chunk": chunk}

    def advance_prefill(self, params, job: dict) -> bool:
        """Advance one chunk of a ``start_prefill`` job; True when every
        prompt entry is in the detached cache.  The first chunk compiles
        like a short prompt; middle chunks share ONE extend compile per
        chunk size; the final partial chunk pow2-pads on KV lanes (``pos``
        pinned back, bit-exact) and runs exact-length on recurrent lanes,
        so the whole job's compile set is O(log chunk), not O(prompt)."""
        entries, done, C = job["entries"], job["done"], job["chunk"]
        take = min(C, entries.size - done)
        toks = entries[done:done + take]
        if job["cache"] is None:
            _, cache = self._jit_prefill(params, jnp.asarray(toks[None]),
                                         max_seq=job["max_seq"])
        else:
            Tp = min(pow2_steps(take, C), job["max_seq"] - done) \
                if self._bucket_prefill else take
            if Tp > take:
                toks = np.concatenate([toks, np.zeros(Tp - take, np.int32)])
            _, cache = self._jit_extend(params, jnp.asarray(toks[None]),
                                        job["cache"])
            if Tp > take:
                cache = {**cache,
                         "pos": jnp.full_like(cache["pos"], done + take)}
        job["cache"] = cache
        job["done"] = done + take
        return job["done"] >= entries.size

    def advance_prefill_row(self, params, job: dict, rows, b: int):
        """``advance_prefill`` on a paged lane whose model keeps recurrent
        state rows (``Model.recurrent_rows``): the chunk, padded to the
        chunk length, advances slot ``b``'s own row of ``rows`` in place
        (the job's first chunk starts it afresh), and the job's detached
        cache holds the attention layers' K/V alone.  No detached copy of
        the state exists, so a batch of prefills costs only their K/V.
        One program for every chunk of every prompt.  Returns (finished,
        rows)."""
        entries, done, C = job["entries"], job["done"], job["chunk"]
        take = min(C, entries.size - done)
        padded = np.zeros((1, min(C, job["max_seq"] - done)), np.int32)
        padded[0, :take] = entries[done:done + take]
        kv = job["cache"]
        if kv is None:
            kv = self._jit_kv_zeros(job["max_seq"])
        job["cache"], rows = self._jit_extend_row(
            params, jnp.asarray(padded), kv, rows, jnp.int32(b),
            jnp.int32(take), jnp.bool_(done == 0))
        job["done"] = done + take
        return job["done"] >= entries.size, rows

    def make_state(self, params, batch: int, slot_len: int, *,
                   need_tokens: Optional[Sequence[int]] = None,
                   num_blocks: Optional[int] = None) -> SequenceState:
        """Build this lane's decode-state adapter.  ``need_tokens``
        (escalation groups) sizes a paged pool to exactly the group's
        residency instead of the worst case."""
        if self.layout == "recurrent":
            return self._place(RecurrentState(self, params, batch, slot_len),
                               batch)
        if self.layout == "dense":
            return self._place(DenseKV(self, params, batch, slot_len), batch)
        paged = PagedState if self.model.recurrent_rows else PagedKV
        shards = self.data_shards if batch % max(self.data_shards, 1) == 0 \
            else 1
        if num_blocks is None and need_tokens is not None:
            if shards > 1:
                # per-shard demand: slot i lives on shard i // (batch/S), so
                # size every shard's range to the HEAVIEST shard (pools are
                # uniform) and pow2-bucket that for compile-shape reuse
                spb = batch // shards
                per = [0] * shards
                for i, t in enumerate(need_tokens):
                    per[i // spb] += blocks_for(t, self.block_size)
                num_blocks = shards * (1 + pow2_steps(max(per), 1 << 30))
            else:
                needed = sum(blocks_for(t, self.block_size)
                             for t in need_tokens)
                # pow2-bucket the pool so escalation groups with different
                # residencies reuse one compiled scan/spec-round shape (the
                # peak-bytes stat tracks LIVE blocks, not this capacity)
                num_blocks = 1 + pow2_steps(needed, 1 << 30)
        return self._place(
            paged(self, params, batch, slot_len, self.block_size,
                  num_blocks, data_shards=shards, kv_ways=self.kv_ways),
            batch)

    def _place(self, state: SequenceState, batch: int) -> SequenceState:
        """Pin a fresh state's device arrays to the mesh (no-op off-mesh):
        paged pools get block-dim/data + kv-head/'model' sharding, dense
        and recurrent stacks the batch/data + head/'model' rules."""
        if self.mesh is None:
            return state
        if state.layout == "paged":
            sh = paged_cache_shardings(state.caches, self.mesh,
                                       self.model.cfg, state.data_shards)
        else:
            sh = cache_shardings(state.caches, self.mesh, self.model.cfg,
                                 batch)
        state.caches = jax.device_put(state.caches, sh)
        return state
