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
table — the BlockSpec index map reads ``table[b, i]`` to aim the next DMA,
so the gather never materializes.  Same online-softmax accumulation; cache
position ``i*bs + off`` masking is identical because block ``i`` holds
logical positions [i*bs, (i+1)*bs).  Its grid is (B, blocks): one step
reads a whole (bs, Kv, hd) pool block and covers every kv head.
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
def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, bs: int, ns: int, Kv: int,
                  window: int, scale: float):
    b = pl.program_id(0)
    isb = pl.program_id(1)

    @pl.when(isb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    # windowed variant: the grid only walks the trailing-window blocks,
    # starting at logical block sb = max(length - window, 0) // bs
    sb = jnp.maximum(length - window, 0) // bs if window else 0
    k_pos = (sb + isb) * bs + \
        jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0]
    mask = k_pos < length
    if window:
        mask = mask & (k_pos >= length - window)

    # one grid step holds the whole (bs, Kv, hd) pool block: walk its kv
    # heads statically, each a (G, hd) x (bs, hd) tile on the MXU
    for g in range(Kv):
        q = q_ref[0, g].astype(jnp.float32)          # (G, hd)
        k = k_ref[0, :, g, :].astype(jnp.float32)    # (bs, hd)
        v = v_ref[0, :, g, :].astype(jnp.float32)    # (bs, hd)
        s = jnp.where(mask, (q @ k.T) * scale, NEG)  # (G, bs)

        m_prev = m_ref[g]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=1)
        acc_ref[g] = acc_ref[g] * alpha[:, None] + p @ v
        m_ref[g] = m_new

    @pl.when(isb == ns - 1)
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
    backing logical positions [i*bs, (i+1)*bs) of that sequence; unused
    entries may point anywhere allocated-or-trap, their positions being
    masked); length: (B,) int32 valid cache entries.  Returns
    (B, Kv, G, hd).

    The block table and lengths ride scalar prefetch: the k/v index maps
    dereference ``table[b, i]`` so each grid step DMAs exactly the one
    block it needs — the paged gather costs no extra HBM traffic over the
    dense kernel.  Grid (B, ns): a step reads one whole ``(bs, Kv, hd)``
    pool block, whose trailing two dims are the pool's own — the TPU
    block-shape rule — and covers every kv head of that sequence.

    With ``window`` > 0 only the trailing ``window`` cache positions are
    attended (sliding-window decode): the grid's sequence axis shrinks to
    the few blocks that can overlap the window, and the index maps offset
    the block-table lookup by the per-sequence start block
    ``max(length - window, 0) // bs`` — long-context sliding-window
    serving reads O(window) bytes per step, not O(length).  Blocks the
    clamp pushes past the table edge read a masked (all-NEG) garbage
    block, contributing exact zeros to the online softmax.
    """
    B, Kv, G, hd = q.shape
    NB, bs, Kv2, hd2 = k_pool.shape
    assert (Kv2, hd2) == (Kv, hd), (k_pool.shape, q.shape)
    MB = table.shape[1]
    scale = 1.0 / np.sqrt(hd)

    # sequence-axis grid: every block (full attention) or just the blocks
    # a trailing window can straddle
    ns = MB if not window else min(MB, (window + bs - 2) // bs + 1)

    def blk(b, i, tbl, ln):
        if window:
            i = jnp.minimum(jnp.maximum(ln[b] - window, 0) // bs + i, MB - 1)
        return (tbl[b, i], 0, 0, 0)

    kern = functools.partial(_paged_kernel, bs=bs, ns=ns, Kv=Kv,
                             window=window, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, ns),
        in_specs=[
            pl.BlockSpec((1, Kv, G, hd), lambda b, i, tbl, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, Kv, hd), blk),
            pl.BlockSpec((1, bs, Kv, hd), blk),
        ],
        out_specs=pl.BlockSpec((1, Kv, G, hd),
                               lambda b, i, tbl, ln: (b, 0, 0, 0)),
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
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table, length, q, k_pool, v_pool)
