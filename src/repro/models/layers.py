"""Shared neural-net layers (pure functions over param pytrees).

Conventions:
  * params are nested dicts of jnp arrays; layer-stacked params have a
    leading ``L`` axis and are consumed via ``lax.scan``.
  * activations run in ``cfg.activ_dtype``; softmax/normalization in f32.
  * attention layout: q (B, S, H, hd); kv (B, S, Kv, hd); GQA groups G=H/Kv.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import runtime


# ----------------------------------------------------------------- init utils
def dense_init(rng, shape, scale: float = 1.0, dtype=jnp.float32):
    # fan_in is the next-to-last dim for matrices / batched matrices (E,d,f).
    fan_in = shape[-2] if len(shape) >= 2 else shape[0]
    std = scale / np.sqrt(fan_in)
    return (jax.random.normal(rng, shape) * std).astype(dtype)


def split(rng, n):
    return list(jax.random.split(rng, n))


# ----------------------------------------------------------------- norms
def rmsnorm(x, weight, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layernorm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def groupnorm_heads(x, weight, eps: float = 1e-5):
    """Per-head group norm used by xLSTM cell outputs. x: (..., H, hd)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float = 10_000.0):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float = 10_000.0):
    """x: (B, S, H, hd); positions: (S,) or (B, S) int32."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), dtype=jnp.float32)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]   # (S, hd/2)
        ang = ang[None, :, None, :]                                      # (1,S,1,hd/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs           # (B,S,hd/2)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention
def init_attention(rng, cfg, dtype):
    d, hd, H, Kv = cfg.d_model, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    r = split(rng, 4)
    return {
        "wq": dense_init(r[0], (d, H * hd), dtype=dtype),
        "wk": dense_init(r[1], (d, Kv * hd), dtype=dtype),
        "wv": dense_init(r[2], (d, Kv * hd), dtype=dtype),
        "wo": dense_init(r[3], (H * hd, d), dtype=dtype),
    }


def _attn_mask(q_pos, k_pos, *, causal: bool, window: int, prefix_len: int):
    """Boolean mask (..., Sq, Sk): True = attend."""
    m = jnp.ones(q_pos.shape[-1:] + k_pos.shape[-1:], dtype=bool)
    if causal:
        m = k_pos[None, :] <= q_pos[:, None]
        if prefix_len:
            # prefix-LM: bidirectional over the first `prefix_len` positions
            m = m | (k_pos[None, :] < prefix_len)
    if window:
        m = m & (k_pos[None, :] > q_pos[:, None] - window)
    return m


def mha(q, k, v, mask=None, softcap: float = 0.0):
    """q: (B,Sq,H,hd), k/v: (B,Sk,Kv,hd). Returns (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Kv = k.shape[2]
    G = H // Kv
    qf = q.reshape(B, Sq, Kv, G, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qf, kf) / np.sqrt(hd)
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def mha_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                prefix_len: int = 0, bq: int = 512, bk: int = 512):
    """Flash-style chunked attention in pure jnp (double lax.scan with online
    softmax).  Memory O(BQ*BK) per step instead of O(Sq*Sk) — the XLA
    equivalent of the Pallas flash kernel, used for long prefills where the
    full score matrix cannot be materialized.

    q: (B,Sq,H,hd); k/v: (B,Sk,Kv,hd). Returns (B,Sq,H,hd).
    NOTE: computes all (Sq/bq)x(Sk/bk) blocks including fully-masked ones
    (baseline; block-skipping is a recorded perf iteration).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    Kv = k.shape[2]
    G = H // Kv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(hd)

    qb = q.reshape(B, nq, bq, Kv, G, hd).astype(jnp.float32)
    kb = k.reshape(B, nk, bk, Kv, hd).astype(jnp.float32)
    vb = v.reshape(B, nk, bk, Kv, hd).astype(jnp.float32)

    def q_block(_, iq):
        qq = qb[:, iq]                                     # (B,bq,Kv,G,hd)
        q_pos = iq * bq + jnp.arange(bq)

        def kv_block(carry, ik):
            m_run, l_run, acc = carry
            kk = kb[:, ik]                                 # (B,bk,Kv,hd)
            vv = vb[:, ik]
            k_pos = ik * bk + jnp.arange(bk)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qq, kk) * scale
            msk = jnp.ones((bq, bk), bool)
            if causal:
                msk = k_pos[None, :] <= q_pos[:, None]
                if prefix_len:
                    msk = msk | (k_pos[None, :] < prefix_len)
            if window:
                msk = msk & (k_pos[None, :] > q_pos[:, None] - window)
            s = jnp.where(msk, s, -1e30)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bkgqs,bskh->bkgqh", p, vv)
            return (m_new, l_new, acc), None

        init = (jnp.full((B, Kv, G, bq), -1e30, jnp.float32),
                jnp.zeros((B, Kv, G, bq), jnp.float32),
                jnp.zeros((B, Kv, G, bq, hd), jnp.float32))
        (m_f, l_f, acc), _ = jax.lax.scan(kv_block, init, jnp.arange(nk))
        out = acc / jnp.maximum(l_f, 1e-20)[..., None]     # (B,Kv,G,bq,hd)
        return None, out

    _, outs = jax.lax.scan(q_block, None, jnp.arange(nq))  # (nq,B,Kv,G,bq,hd)
    out = jnp.moveaxis(outs, 0, 1)                          # (B,nq,Kv,G,bq,hd)
    out = jnp.moveaxis(out, -2, 2)                          # (B,nq,bq,Kv,G,hd)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


# Sequence length above which prefill/train attention switches to the
# chunked (flash-equivalent) path.  Perf iteration #5 tried 4096 and was
# REFUTED: at train_4k the chunked double-scan's per-block dynamic slices
# sit at fusion boundaries, where both our analyzer and XLA's cost model
# charge full-operand traffic — measured memory term rose 5x
# (EXPERIMENTS.md §Perf).  8192 keeps chunking where it is essential
# (32k prefill) and the dense mha path where the (S,S) scores still fit.
CHUNKED_ATTN_THRESHOLD = 8192


def attention_block(p, x, positions, cfg, *, causal: bool = True,
                    window: int = 0, prefix_len: int = 0, rope_theta=None):
    """Full (prefill / train) attention. x: (B,S,d) -> (B,S,d), plus (k,v).
    Long sequences (>= CHUNKED_ATTN_THRESHOLD) take the flash-equivalent
    chunked path; short ones materialize the (S,S) mask directly."""
    B, S, d = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, Kv, hd)
    v = (x @ p["wv"]).reshape(B, S, Kv, hd)
    if cfg.use_rope:
        theta = rope_theta if rope_theta is not None else cfg.rope_theta
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    if S >= CHUNKED_ATTN_THRESHOLD:
        out = mha_chunked(q, k, v, causal=causal, window=window,
                          prefix_len=prefix_len)
    else:
        mask = _attn_mask(positions, positions, causal=causal, window=window,
                          prefix_len=prefix_len) if causal or window else None
        out = mha(q, k, v, mask=mask)
    return out.reshape(B, S, H * hd) @ p["wo"], (k, v)


def decode_attention(p, x, cache_k, cache_v, pos, cfg, *, window: int = 0):
    """Single-token decode. x: (B,1,d); cache_k/v: (B,Smax,Kv,hd); pos ().

    Returns (out (B,1,d), new_k, new_v). With ``window`` > 0, only the last
    ``window`` cache entries are read (sliding-window decode for long ctx).
    """
    B, _, d = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    k = (x @ p["wk"]).reshape(B, 1, Kv, hd)
    v = (x @ p["wv"]).reshape(B, 1, Kv, hd)
    if cfg.use_rope:
        pp = jnp.full((1,), pos, dtype=jnp.int32)
        q = apply_rope(q, pp, cfg.rope_theta)
        k = apply_rope(k, pp, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, axis=1)
    if window:
        start = jnp.maximum(pos - (window - 1), 0)
        kk = jax.lax.dynamic_slice_in_dim(cache_k, start, window, axis=1)
        vv = jax.lax.dynamic_slice_in_dim(cache_v, start, window, axis=1)
        k_pos = start + jnp.arange(window)
    else:
        kk, vv = cache_k, cache_v
        k_pos = jnp.arange(cache_k.shape[1])
    mask = (k_pos <= pos)[None, None, None, None, :]   # (1,1,1,1,Sk) over bkgqs
    out = mha(q, kk, vv, mask=mask)
    return out.reshape(B, 1, H * hd) @ p["wo"], cache_k, cache_v


def paged_extend_attention(p, x, k_pool, v_pool, table, pos, cfg):
    """Cached decode through a paged KV pool (whole batch at once; T=1 is
    the single-token decode step, T>1 the speculative verify).

    x: (B,T,d); k_pool/v_pool: (NB, bs, Kv, hd) — ONE block pool shared by
    all sequences; table: (B, MB) int32 block table (logical position ``t``
    of sequence ``b`` lives in block ``table[b, t // bs]`` at offset
    ``t % bs``); pos: (B,) per-sequence write position.

    Unlike the dense paths (scalar ``pos``, vmapped per slot), this is
    inherently batched: the pool has no leading batch axis, so the new K/V
    land via one advanced-indexing scatter and the read is a (B, MB)
    block-table gather.  Out-of-range positions (a retired slot
    garbage-decoding past its table) clamp to the last table entry, which
    the scheduler keeps pointed at the trap block.  Intra-block attention
    is causal, windowed by ``cfg.sliding_window`` exactly like the dense
    decode (block masks / token trees stay on the dense layout).
    Returns (out (B,T,d), new_k_pool, new_v_pool).
    """
    B, T, d = x.shape
    _, bs, Kv, hd = k_pool.shape
    H = cfg.num_heads
    q = (x @ p["wq"]).reshape(B, T, H, hd)
    k = (x @ p["wk"]).reshape(B, T, Kv, hd)
    v = (x @ p["wv"]).reshape(B, T, Kv, hd)
    q_pos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]   # (B, T)
    if cfg.use_rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    blk = jnp.take_along_axis(table, q_pos // bs, axis=1)            # (B, T)
    off = q_pos % bs
    k_pool = k_pool.at[blk, off].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[blk, off].set(v.astype(v_pool.dtype))
    kk = k_pool[table].reshape(B, -1, Kv, hd)
    vv = v_pool[table].reshape(B, -1, Kv, hd)
    k_pos = jnp.arange(kk.shape[1], dtype=jnp.int32)
    mask = k_pos[None, None, :] <= q_pos[:, :, None]                 # (B,T,S)
    if cfg.sliding_window:
        mask = mask & (k_pos[None, None, :] >
                       q_pos[:, :, None] - cfg.sliding_window)
    out = mha(q, kk, vv, mask=mask[:, None, None, :, :])
    return out.reshape(B, T, H * hd) @ p["wo"], k_pool, v_pool


def paged_decode_attention_block(p, x, k_pool, v_pool, table, pos, cfg, *,
                                 backend: str = "auto"):
    """Single-token decode through a paged KV pool WITHOUT materializing the
    block-table gather.

    Same write path as ``paged_extend_attention`` (the new K/V land at
    ``table[b, pos // bs]``, offset ``pos % bs``), but the read dispatches
    on backend: TPU runs the flash-decoding Pallas kernel
    (``kernels.ops.paged_decode_attention`` — scalar-prefetched block-table
    index maps, each grid step DMAs a group of the blocks below the
    sequence's length), CPU runs its
    pure-jnp oracle ``kernels.ref.paged_decode_attention_ref``.  ``backend``
    "kernel" / "ref" force a side (tests); "auto" picks by device.
    ``cfg.sliding_window`` configs run the kernel's windowed variant
    (trailing-window blocks only) — the masked full-width gather is no
    longer on any T=1 decode path.

    x: (B, 1, d); k_pool/v_pool: (NB, bs, Kv, hd); table: (B, MB) int32;
    pos: (B,).  Returns (out (B, 1, d), new_k_pool, new_v_pool).
    """
    from repro.kernels import ops, ref
    B, T, d = x.shape
    assert T == 1, "paged_decode_attention_block is the T=1 fast path"
    _, bs, Kv, hd = k_pool.shape
    H = cfg.num_heads
    G = H // Kv
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    k = (x @ p["wk"]).reshape(B, 1, Kv, hd)
    v = (x @ p["wv"]).reshape(B, 1, Kv, hd)
    q_pos = pos[:, None]                                             # (B, 1)
    if cfg.use_rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    blk = jnp.take_along_axis(table, q_pos // bs, axis=1)[:, 0]      # (B,)
    off = (pos % bs)
    k_pool = k_pool.at[blk, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[blk, off].set(v[:, 0].astype(v_pool.dtype))
    qh = q[:, 0].reshape(B, Kv, G, hd)          # head h = kv*G + g, as mha
    length = pos + 1
    win = cfg.sliding_window
    if backend == "kernel" or (backend == "auto" and not ops.on_cpu()):
        # under a mesh each shard runs the kernel on its own slots and kv
        # heads; pool blocks stay whole over the data axes, since the
        # table holds global block ids
        dp, mp = runtime.kernel_axes(B, Kv)
        attn = runtime.shard_kernel(
            functools.partial(ops.paged_decode_attention, window=win),
            in_specs=(P(dp, mp), P(None, None, mp), P(None, None, mp),
                      P(dp), P(dp)),
            out_specs=P(dp, mp))
        out = attn(qh, k_pool, v_pool, table, length)
    else:
        out = ref.paged_decode_attention_ref(qh, k_pool, v_pool, table,
                                             length, window=win)
    out = out.reshape(B, 1, H * hd).astype(x.dtype)
    return out @ p["wo"], k_pool, v_pool


def extend_attention(p, x, cache_k, cache_v, pos, cfg, *, window: int = 0,
                     block_mask=None, q_positions=None):
    """Multi-token cached decode (chunked prefill / speculative verify).

    x: (B,T,d); new k/v written into the cache at [pos, pos+T).  By default
    intra-block attention is causal; ``block_mask`` (T,C) with C >= T
    overrides it — its LAST T columns align with the new tokens, earlier
    columns cover tokens already in the cache at [pos-(C-T), pos) (token
    trees drafted level by level; one-shot verification passes C == T) —
    and ``q_positions`` (T,) overrides the RoPE positions (token-tree
    nodes use tree base + node depth).
    Returns (out (B,T,d), new_k, new_v).
    """
    B, T, d = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Smax = cache_k.shape[1]
    q = (x @ p["wq"]).reshape(B, T, H, hd)
    k = (x @ p["wk"]).reshape(B, T, Kv, hd)
    v = (x @ p["wv"]).reshape(B, T, Kv, hd)
    q_pos = (pos + jnp.arange(T, dtype=jnp.int32)) if q_positions is None \
        else jnp.asarray(q_positions, jnp.int32)
    if cfg.use_rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, axis=1)
    k_pos = jnp.arange(Smax, dtype=jnp.int32)
    if block_mask is None:
        mask = k_pos[None, :] <= q_pos[:, None]                     # (T, Smax)
    else:
        from repro.kernels import ops
        if not ops.on_cpu():
            # token-tree verify on TPU: the flash-decoding tree kernel
            # streams the cache once instead of materializing the
            # (T, Smax) mask; CPU keeps the jnp masked-mha path below
            G = H // Kv
            qh = jnp.transpose(q.reshape(B, T, Kv, G, hd), (0, 2, 3, 1, 4))
            dp, mp = runtime.kernel_axes(B, Kv)
            attn = runtime.shard_kernel(
                functools.partial(ops.tree_verify_attention, window=window),
                in_specs=(P(dp, mp), P(dp, mp), P(dp, mp), P(dp), P(),
                          P(dp)),
                out_specs=P(dp, mp))
            out = attn(qh, jnp.moveaxis(cache_k, 2, 1),
                       jnp.moveaxis(cache_v, 2, 1),
                       jnp.broadcast_to(pos, (B,)), block_mask,
                       jnp.broadcast_to(q_pos, (B, T)))
            out = jnp.transpose(out, (0, 3, 1, 2, 4)).astype(x.dtype)
            return out.reshape(B, T, H * hd) @ p["wo"], cache_k, cache_v
        off = block_mask.shape[1] - T            # tree nodes already cached
        base = k_pos[None, :] < pos - off                            # cached part
        placed = jax.lax.dynamic_update_slice(
            jnp.zeros((T, Smax), bool), block_mask.astype(bool),
            (0, pos - off))
        mask = base | placed
    if window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    out = mha(q, cache_k, cache_v, mask=mask)
    return out.reshape(B, T, H * hd) @ p["wo"], cache_k, cache_v


def cross_attention_kv(p, enc, cfg):
    """Precompute cross-attention k/v from encoder output. enc: (B,Se,d)."""
    B, Se, _ = enc.shape
    Kv, hd = cfg.num_kv_heads, cfg.head_dim
    k = (enc @ p["wk"]).reshape(B, Se, Kv, hd)
    v = (enc @ p["wv"]).reshape(B, Se, Kv, hd)
    return k, v


def cross_attention(p, x, k, v, cfg):
    """x: (B,Sq,d) attends over fixed (k, v). No mask (encoder fully visible)."""
    B, Sq, d = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, Sq, H, hd)
    out = mha(q, k, v, mask=None)
    return out.reshape(B, Sq, H * hd) @ p["wo"]


# ----------------------------------------------------------------- mlp
def init_mlp(rng, cfg, dtype):
    d, f = cfg.d_model, cfg.d_ff
    r = split(rng, 3)
    if cfg.mlp_activation in ("silu", "geglu"):
        return {
            "w_gate": dense_init(r[0], (d, f), dtype=dtype),
            "w_up": dense_init(r[1], (d, f), dtype=dtype),
            "w_down": dense_init(r[2], (f, d), dtype=dtype),
        }
    return {   # relu2 / gelu: single up projection
        "w_up": dense_init(r[0], (d, f), dtype=dtype),
        "w_down": dense_init(r[1], (f, d), dtype=dtype),
    }


def mlp_block(p, x, activation: str):
    if activation == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif activation == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    elif activation == "gelu":
        h = jax.nn.gelu(x @ p["w_up"])
    else:
        raise ValueError(activation)
    return h @ p["w_down"]


# ----------------------------------------------------------------- embeddings
def init_embedding(rng, vocab: int, d: int, dtype):
    # std 0.02, GPT-style; keeps tied-head logits O(1) at init for any vocab.
    return (jax.random.normal(rng, (vocab, d)) * 0.02).astype(dtype)


def embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed(table_or_head, h):
    """h: (..., d) -> logits (..., V) in f32."""
    return jnp.einsum("...d,vd->...v", h.astype(jnp.float32),
                      table_or_head.astype(jnp.float32))
