"""Tree-verification attention Pallas TPU kernel.

Speculative decoding with token trees verifies N candidate tokens per
sequence in ONE target forward: the tree's K/V are appended to the cache at
positions [length, length+N) and every node-query attends (a) the whole
committed cache prefix and (b) its own ancestor chain inside the tree —
the packed ancestor mask from ``TokenTree.attention_mask``.  The mask may
be rectangular (N, C) with C >= N: incremental level drafting extends only
a level's N new nodes while masking against the C-N tree nodes earlier
levels already wrote to the cache.

Same flash-decoding skeleton as ``decode_attention``: grid (B, Kv, S//BS),
sequence axis walked with a running max/denominator in VMEM scratch.  The
per-block novelty is the mask: cache positions use the usual
``k_pos < length`` prefix test, while positions that fall inside the tree
region look up their ancestor-mask column.  The column gather has a
data-dependent start (``length`` differs per sequence), so it is phrased
as a one-hot matmul — ``tree_mask @ onehot(k_pos - length)`` — which the
MXU eats for free at tree widths (N <= 64) instead of a serialized VMEM
gather.

``q_pos`` carries the per-node RoPE positions (length + node depth) so
sliding-window masking stays depth-correct: a node at depth d sees exactly
the window a linear decode at position length+d would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, tm_ref, qp_ref, o_ref,
            acc_ref, m_ref, l_ref, *, bs: int, ns: int, N: int, C: int,
            G: int, hd: int, window: int, scale: float):
    b = pl.program_id(0)
    isb = pl.program_id(2)

    @pl.when(isb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32).reshape(G * N, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (BS, hd)
    v = v_ref[0, 0].astype(jnp.float32)              # (BS, hd)
    s = (q @ k.T) * scale                            # (G*N, BS)

    base = len_ref[b, 0] - (C - N)                   # tree start in the cache
    k_pos = isb * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0]
    in_cache = k_pos < base                          # (BS,)
    # tree region [base, base+C): column j of the ancestor mask governs
    # the key at cache position base+j.  One-hot matmul in place of the
    # per-sequence dynamic gather.
    t = k_pos - base                                 # (BS,)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (C, bs), 0)
              == t[None, :]).astype(jnp.float32)     # (C, BS); off-range -> 0
    tree_cols = (tm_ref[...].astype(jnp.float32) @ onehot) > 0.5   # (N, BS)
    mask = in_cache[None, :] | tree_cols             # (N, BS)
    if window:
        qp = qp_ref[0, 0]                            # (N,)
        mask = mask & (k_pos[None, :] > qp[:, None] - window)
    mask = jnp.broadcast_to(mask[None], (G, N, bs)).reshape(G * N, bs)
    s = jnp.where(mask, s, NEG)

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
                       ).reshape(G, N, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bs", "interpret"))
def tree_verify_attention(q, k, v, length, tree_mask, q_pos, *,
                          window: int = 0, bs: int = 512,
                          interpret: bool = False):
    """q: (B, Kv, G, N, hd) — N tree-node queries per kv-head group;
    k, v: (B, Kv, S, hd) — the cache AFTER this call's N tree K/V were
    written at [length, length+N); length: (B,) int32 valid entries BEFORE
    those tokens; tree_mask: (N, C) bool, C >= N — the LAST N columns align
    with the new tokens; earlier columns cover tree nodes already in the
    cache at [length-(C-N), length) (one-shot verify passes C == N);
    q_pos: (B, N) int32 per-node positions (tree base + depth) for
    windowing.  Cache positions >= length+N are masked garbage.  Returns
    (B, Kv, G, N, hd)."""
    B, Kv, G, N, hd = q.shape
    C = tree_mask.shape[1]
    assert C >= N, (N, C)
    S = k.shape[2]
    bs = min(bs, S)
    if S % bs:                                       # pad: tail is masked off
        pad = bs - S % bs
        zp = ((0, 0), (0, 0), (0, pad), (0, 0))
        k = jnp.pad(k, zp)
        v = jnp.pad(v, zp)
        S += pad
    ns = S // bs
    scale = 1.0 / np.sqrt(hd)

    kern = functools.partial(_kernel, bs=bs, ns=ns, N=N, C=C, G=G, hd=hd,
                             window=window, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(B, Kv, ns),
        in_specs=[
            # ``length`` rides whole in SMEM as (B, 1) and each step reads
            # its own row; ``q_pos`` rides as (B, 1, N).  Both keep every
            # block's trailing two dims equal to the array's (the TPU
            # tiling rule), also when vmap prepends a batch axis
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, N, hd), lambda b, g, i: (b, g, 0, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((N, C), lambda b, g, i: (0, 0)),
            pl.BlockSpec((1, 1, N), lambda b, g, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, N, hd), lambda b, g, i: (b, g, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, N, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * N, hd), jnp.float32),
            pltpu.VMEM((G * N,), jnp.float32),
            pltpu.VMEM((G * N,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length.reshape(B, 1), q, k, v, tree_mask.astype(jnp.int32),
      q_pos.astype(jnp.int32).reshape(B, 1, N))
