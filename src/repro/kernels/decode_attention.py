"""GQA decode attention Pallas TPU kernel (flash-decoding style).

The serving hot loop: ONE query token per sequence against a long KV cache
(32k / 500k).  Memory-bound — the kernel's job is to stream the cache
through VMEM exactly once at full HBM bandwidth.

Layout: q (B, Kv, G, hd) — the G = H/Kv query heads of one kv head are a
(G, hd) tile that rides the MXU against each (BS, hd) kv block.
``length`` (B,) masks the valid cache prefix (cache positions >= length are
garbage/unwritten); window w restricts to the trailing w entries.

Grid: (B, Kv, S//BS) — last axis sequential with running max/denominator in
VMEM scratch.

``paged_decode_attention`` is the paged-KV twin (vLLM-style): K/V live in
ONE (NB, bs, Kv, hd) block pool shared by all sequences, and each
sequence's logical block ``i`` is found through a scalar-prefetched block
table — the BlockSpec index maps read ``table[b, i]`` to aim the DMAs,
so the gather never materializes.  Same online-softmax accumulation; cache
position ``i*bs + off`` masking is identical because block ``i`` holds
logical positions [i*bs, (i+1)*bs).  Each sequence walks only the blocks
below its length, a group of blocks per grid step: one step reads several
whole (bs, Kv, hd) pool blocks and covers every kv head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bs: int, ns: int, window: int, scale: float):
    isb = pl.program_id(2)

    @pl.when(isb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)             # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)             # (BS, hd)
    v = v_ref[0, 0].astype(jnp.float32)             # (BS, hd)
    s = (q @ k.T) * scale                            # (G, BS)

    length = len_ref[0]
    k_pos = isb * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0]
    mask = k_pos < length
    if window:
        mask = mask & (k_pos >= length - window)
    s = jnp.where(mask[None, :], s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(isb == ns - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-20)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bs", "interpret"))
def decode_attention(q, k, v, length, *, window: int = 0, bs: int = 512,
                     interpret: bool = False):
    """q: (B, Kv, G, hd); k,v: (B, Kv, S, hd); length: (B,) int32 — number of
    valid cache entries (the query attends to positions < length).
    Returns (B, Kv, G, hd)."""
    B, Kv, G, hd = q.shape
    S = k.shape[2]
    bs = min(bs, S)
    assert S % bs == 0
    ns = S // bs
    scale = 1.0 / np.sqrt(hd)

    kern = functools.partial(_kernel, bs=bs, ns=ns, window=window, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(B, Kv, ns),
        in_specs=[
            pl.BlockSpec((1,), lambda b, g, i: (b,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, i: (b, g, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, g, i: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length, q, k, v)


# ---------------------------------------------------------------- paged
# VMEM for one call's K and V blocks, both double-buffered; the number of
# blocks fetched per DMA wait is what fits in it (``_group_blocks``)
_KV_VMEM_BYTES = 8 * 2**20


def _group_blocks(bs: int, Kv: int, hd: int, dtype, MB: int) -> int:
    """Pool blocks per group: K and V, two buffers each, of ``(bs, Kv, hd)``
    blocks laid out in VMEM tiles (second-minor dim padded to the dtype's
    sublane tile, minor dim to 128 lanes), within ``_KV_VMEM_BYTES``."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * max(4 // item, 1)
    tile = bs * (-(-Kv // sub) * sub) * (-(-hd // 128) * 128) * item
    return max(1, min(MB, _KV_VMEM_BYTES // (4 * tile)))


def _schedule(table, lo, hi, P: int):
    """The grid's steps, each one group of up to P blocks of one sequence.

    Sequence b takes ``max(cdiv(hi - lo, P), 1)`` steps in a row (a
    sequence with no block still takes one, to write its output).  Returns
    the number of steps; per step (padded to B * cdiv(MB, P)) its sequence
    and its group within that sequence's walk, (2, S); and the pool block
    of each of its P group slots, (S * P,).  A slot past hi keeps the
    block it held one group earlier, so the pipeline fetches nothing new,
    or in a sequence's first group takes its last live block: a block
    below the length (where the sequence has one), its positions
    masked."""
    B, MB = table.shape
    S = B * -(-MB // P)
    steps = jnp.maximum((hi - lo + P - 1) // P, 1)
    seq = jnp.repeat(jnp.arange(B), steps, total_repeat_length=S)
    grp = jnp.arange(S) - (jnp.cumsum(steps) - steps)[seq]
    lo_s, hi_s = lo[seq][:, None], hi[seq][:, None]
    i = lo_s + grp[:, None] * P + jnp.arange(P)
    i = jnp.where(i < hi_s, i, jnp.where(i - P >= lo_s, i - P, hi_s - 1))
    blocks = jnp.take_along_axis(table[seq], jnp.clip(i, 0, MB - 1), axis=1)
    return steps.sum(), jnp.stack([seq, grp]), blocks.reshape(-1)


def _paged_kernel(len_ref, walk_ref, step_ref, blk_ref, q_ref, *refs,
                  bs: int, MB: int, P: int, Kv: int, window: int,
                  scale: float):
    k_refs, v_refs = refs[:P], refs[P:2 * P]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * P:]
    t = pl.program_id(0)
    b, g = step_ref[0, t], step_ref[1, t]
    length, lo, hi = len_ref[b], walk_ref[0, b], walk_ref[1, b]
    first = lo + g * P                          # the group's first block

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(first < hi)
    def _group():
        k_pos = first * bs + \
            jax.lax.broadcasted_iota(jnp.int32, (1, P * bs), 1)
        mask = k_pos < jnp.minimum(length, MB * bs)
        if window:
            mask = mask & (k_pos >= length - window)
        # each block holds every kv head: walk them statically, each a
        # (G, hd) x (P*bs, hd) tile on the MXU
        for h in range(Kv):
            q = q_ref[0, h].astype(jnp.float32)                  # (G, hd)
            k = jnp.concatenate([r[0, :, h, :].astype(jnp.float32)
                                 for r in k_refs], axis=0)       # (P*bs, hd)
            v = jnp.concatenate([r[0, :, h, :].astype(jnp.float32)
                                 for r in v_refs], axis=0)
            s = jnp.where(mask, (q @ k.T) * scale, NEG)          # (G, P*bs)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + p @ v
            m_ref[h] = m_new

    @pl.when(first + P >= hi)                   # the sequence's last step
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-20)[..., None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, table, length, *,
                           window: int = 0, interpret: bool = False):
    """Decode attention through a paged KV pool.

    q: (B, Kv, G, hd); k_pool/v_pool: (NB, bs, Kv, hd) — the shared block
    pool; table: (B, MB) int32 block table (entry i holds the pool block
    backing logical positions [i*bs, (i+1)*bs) of that sequence; entries
    at or past a sequence's length are never read); length: (B,) int32
    valid cache entries.  Returns (B, Kv, G, hd).

    Sequence b walks only the logical blocks that hold positions below
    ``length[b]``: ``cdiv(min(length, MB*bs), bs)`` of them, so a short
    sequence in a wide table costs its own length.  The walk goes in
    groups of P blocks (``_group_blocks``: as many as fit a fixed VMEM
    budget at these shapes), one grid step each, and the grid holds just
    the steps the walks need (``_schedule``, a dynamic grid size).  The
    lengths, the walks and each step's pool blocks, read from the block
    table, ride scalar prefetch.  The pools come in P times over, one
    ``(1, bs, Kv, hd)`` block spec per group slot, so the pipeline fetches
    the next step's 2P blocks while this one computes, and the group is
    one (G, hd) x (P*bs, hd) tile per kv head on the MXU.  (The blocks
    are not fetched by hand from an unblocked pool: Mosaic refuses a DMA
    slice of one block whose (Kv, hd) is not tile-aligned, such as
    (3, 64).)

    With ``window`` > 0 only the trailing ``window`` cache positions are
    attended (sliding-window decode): the walk starts at block
    ``max(length - window, 0) // bs``, so long-context sliding-window
    serving reads O(window) bytes per step, not O(length).

    A sequence with nothing to attend (length 0) gets zeros.
    """
    B, Kv, G, hd = q.shape
    NB, bs, Kv2, hd2 = k_pool.shape
    assert (Kv2, hd2) == (Kv, hd), (k_pool.shape, q.shape)
    MB = table.shape[1]
    P = _group_blocks(bs, Kv, hd, k_pool.dtype, MB)
    # the walk: logical blocks [lo, hi) hold the positions below
    # min(length, MB*bs) (the trailing window's, when windowed)
    hi = jnp.minimum((jnp.minimum(length, MB * bs) + bs - 1) // bs, MB)
    lo = jnp.minimum(jnp.maximum(length - window, 0) // bs, hi) \
        if window else jnp.zeros_like(hi)
    n_steps, steps, blocks = _schedule(table, lo, hi, P)

    def group_slot(j):
        return pl.BlockSpec((1, bs, Kv, hd),
                            lambda t, ln, wk, st, blk: (blk[t * P + j],
                                                        0, 0, 0))

    def per_seq(t, ln, wk, st, blk):
        return (st[0, t], 0, 0, 0)

    slots = [group_slot(j) for j in range(P)]
    kern = functools.partial(_paged_kernel, bs=bs, MB=MB, P=P, Kv=Kv,
                             window=window, scale=1.0 / np.sqrt(hd))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((1, Kv, G, hd), per_seq)] + slots + slots,
        out_specs=pl.BlockSpec((1, Kv, G, hd), per_seq),
        scratch_shapes=[
            pltpu.VMEM((Kv, G, hd), jnp.float32),
            pltpu.VMEM((Kv, G), jnp.float32),
            pltpu.VMEM((Kv, G), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(length, jnp.stack([lo, hi]), steps, blocks, q, *[k_pool] * P,
      *[v_pool] * P)
