"""Decoder-only transformer (dense / moe / vlm families).

Scan-over-layers with stacked params keeps the HLO one-layer-sized, which
matters both for the 80 dry-run compiles in this container and for real
compile times on pods.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import runtime
from repro.models import layers as L
from repro.models import moe as MOE


def _dt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _adt(cfg):
    return jnp.dtype(cfg.activ_dtype)


# ----------------------------------------------------------------- init
def init_params(rng, cfg):
    dtype = _dt(cfg)
    r = L.split(rng, cfg.num_layers + 3)

    def one_block(rng_l):
        rr = L.split(rng_l, 2)
        blk = {
            "attn_norm": jnp.zeros((cfg.d_model,), dtype),
            "attn": L.init_attention(rr[0], cfg, dtype),
            "mlp_norm": jnp.zeros((cfg.d_model,), dtype),
        }
        if cfg.family == "moe":
            blk["moe"] = MOE.init_moe(rr[1], cfg, dtype)
        else:
            blk["mlp"] = L.init_mlp(rr[1], cfg, dtype)
        return blk

    # one vmapped block init: the same values as a per-layer loop, built
    # straight into the stacked (L, ...) leaves with no per-layer copies
    blocks = jax.vmap(one_block)(jnp.stack(r[:cfg.num_layers]))
    params = {
        "embed": L.init_embedding(r[-3], cfg.vocab_size, cfg.d_model, dtype),
        "blocks": blocks,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_embedding(r[-2], cfg.vocab_size, cfg.d_model, dtype)
    return params


def _head(params):
    return params.get("lm_head", params["embed"])


# ----------------------------------------------------------------- blocks
def _block(p, h, positions, cfg, mask):
    window, prefix_len = mask   # (window, prefix_len); causal always True here
    h = runtime.shard_activation(h)
    a, _kv = L.attention_block(p["attn"], L.rmsnorm(h, p["attn_norm"], cfg.norm_eps),
                               positions, cfg, window=window,
                               prefix_len=prefix_len)
    h = h + a
    hn = L.rmsnorm(h, p["mlp_norm"], cfg.norm_eps)
    if cfg.family == "moe":
        m, aux = MOE.moe_apply(p["moe"], hn, cfg)
    else:
        m, aux = L.mlp_block(p["mlp"], hn, cfg.mlp_activation), jnp.float32(0.0)
    return h + m, aux, _kv


# ----------------------------------------------------------------- forward
def forward(params, tokens, cfg, *, embeds=None, window: int = 0,
            remat: bool = False, collect_hidden: bool = False):
    """Training / scoring forward pass.

    tokens: (B, S_text) int32.  For vlm, ``embeds`` (B, P, d) is prepended
    (prefix-LM bidirectional attention over the prefix).
    Returns (logits (B, S_total, V) f32, aux_loss, hidden?) .
    """
    h = L.embed(params["embed"], tokens).astype(_adt(cfg))
    prefix_len = 0
    if embeds is not None:
        h = jnp.concatenate([embeds.astype(h.dtype), h], axis=1)
        prefix_len = embeds.shape[1]
    S = h.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    mask = (window or cfg.sliding_window, prefix_len)

    def body(carry, p):
        hh, aux = carry
        hh, a, _ = _block(p, hh, positions, cfg, mask)
        y = hh if collect_hidden else jnp.zeros((), hh.dtype)
        return (hh, aux + a), y

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (h, aux), hs = jax.lax.scan(body, (h, jnp.float32(0.0)), params["blocks"])
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(_head(params), h)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    if collect_hidden:
        return logits, aux, hs
    return logits, aux


# ----------------------------------------------------------------- cache
def init_cache(cfg, batch: int, max_seq: int, dtype=None):
    dtype = dtype or _dt(cfg)
    shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def init_paged_cache(cfg, num_blocks: int, block_size: int, batch: int,
                     max_blocks: int, dtype=None):
    """Paged twin of ``init_cache``: ONE (num_blocks, block_size) K/V pool
    per layer shared by all ``batch`` sequences, a per-sequence block table
    (padded with the trap block 0) and per-sequence write positions.  See
    ``core/paged_cache.py`` for the allocation protocol."""
    dtype = dtype or _dt(cfg)
    shape = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads,
             cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "table": jnp.zeros((batch, max_blocks), jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def paged_decode_step(params, token, cache, cfg, *, attn_backend: str = "auto"):
    """One decode step over a paged cache. token: (B, 1) int32; cache as
    built by ``init_paged_cache``.  Returns (logits (B, V), cache).

    The batched counterpart of vmapping ``decode_step`` over stacked dense
    slots: same math, but K/V are read and written through the block table
    so per-sequence capacity is whatever the scheduler allocated.  The
    attention read dispatches per backend (TPU: the Pallas flash-decoding
    paged kernel — including its windowed variant for
    ``cfg.sliding_window`` configs; CPU: the pure-jnp oracle) instead of
    gathering the full block-table width every step.  Only
    ``attn_backend="gather"`` keeps the general T=1 ``paged_extend_step``
    path (the parity oracle for tests) — no config falls off the kernel
    fast path."""
    if attn_backend != "gather":
        return _paged_decode_step_kernel(params, token, cache, cfg,
                                         attn_backend)
    logits, cache = paged_extend_step(params, token, cache, cfg)
    return logits[:, 0], cache


def _paged_decode_step_kernel(params, token, cache, cfg, backend: str):
    """T=1 paged decode with the dispatched attention read
    (``layers.paged_decode_attention_block``)."""
    h = L.embed(params["embed"], token).astype(_adt(cfg))
    pos, table = cache["pos"], cache["table"]

    def body(hh, xs):
        p, ck, cv = xs
        hh = runtime.shard_activation(hh)
        hn = L.rmsnorm(hh, p["attn_norm"], cfg.norm_eps)
        a, ck, cv = L.paged_decode_attention_block(p["attn"], hn, ck, cv,
                                                   table, pos, cfg,
                                                   backend=backend)
        hh = hh + a
        hn = L.rmsnorm(hh, p["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = MOE.moe_apply(p["moe"], hn, cfg)
        else:
            m = L.mlp_block(p["mlp"], hn, cfg.mlp_activation)
        return hh + m, (ck, cv)

    h, (ks, vs) = jax.lax.scan(body, h, (params["blocks"], cache["k"],
                                         cache["v"]))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    # pin the paged serving path's logits to batch sharding: the lm_head
    # contraction is vocab-sharded over 'model', and without this XLA defers
    # a vocab-sharded (B, V) tensor to the sampler's argmax/categorical
    logits = runtime.shard_activation(L.unembed(_head(params), h[:, 0, :]))
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, {**cache, "k": ks, "v": vs, "pos": pos + 1}


def paged_extend_step(params, tokens, cache, cfg):
    """Multi-token cached decode over a paged cache (speculative verify).
    tokens (B, T) -> (logits (B, T, V), cache)."""
    h = L.embed(params["embed"], tokens).astype(_adt(cfg))
    pos, table = cache["pos"], cache["table"]
    T = tokens.shape[1]

    def body(hh, xs):
        p, ck, cv = xs
        hh = runtime.shard_activation(hh)
        hn = L.rmsnorm(hh, p["attn_norm"], cfg.norm_eps)
        a, ck, cv = L.paged_extend_attention(p["attn"], hn, ck, cv, table,
                                             pos, cfg)
        hh = hh + a
        hn = L.rmsnorm(hh, p["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = MOE.moe_apply(p["moe"], hn, cfg)
        else:
            m = L.mlp_block(p["mlp"], hn, cfg.mlp_activation)
        return hh + m, (ck, cv)

    h, (ks, vs) = jax.lax.scan(body, h, (params["blocks"], cache["k"],
                                         cache["v"]))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    # batch-shard the verify logits for the same reason as the decode step
    logits = runtime.shard_activation(L.unembed(_head(params), h))
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, {**cache, "k": ks, "v": vs,
                    "pos": pos + jnp.asarray(T, jnp.int32)}


def prefill(params, tokens, cfg, *, max_seq: Optional[int] = None,
            embeds=None, window: int = 0):
    """Run the prompt, build the KV cache. Returns (last-token logits, cache)."""
    h = L.embed(params["embed"], tokens).astype(_adt(cfg))
    prefix_len = 0
    if embeds is not None:
        h = jnp.concatenate([embeds.astype(h.dtype), h], axis=1)
        prefix_len = embeds.shape[1]
    S = h.shape[1]
    max_seq = max_seq or S
    positions = jnp.arange(S, dtype=jnp.int32)
    mask = (window or cfg.sliding_window, prefix_len)

    def body(carry, p):
        hh = carry
        hh, _aux, (k, v) = _block(p, hh, positions, cfg, mask)
        return hh, (k, v)

    h, (ks, vs) = jax.lax.scan(body, h, params["blocks"])
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(_head(params), h[:, -1:, :])[:, 0]
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    pad = max_seq - S
    if pad > 0:
        zpad = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
        ks = jnp.pad(ks, zpad)
        vs = jnp.pad(vs, zpad)
    cache = {"k": ks.astype(_dt(cfg)), "v": vs.astype(_dt(cfg)),
             "pos": jnp.asarray(S, jnp.int32)}
    return logits, cache


def extend_step(params, tokens, cache, cfg, *, window: int = 0, block_mask=None,
                q_positions=None):
    """Multi-token cached decode. tokens (B,T) -> (logits (B,T,V), cache).
    ``block_mask`` (T,C), C >= T, customizes intra-block attention (its
    last T columns are the new tokens, earlier columns cover tree nodes
    already in the cache — see layers.extend_attention); ``q_positions``
    overrides RoPE positions (token trees)."""
    h = L.embed(params["embed"], tokens).astype(_adt(cfg))
    pos = cache["pos"]
    T = tokens.shape[1]

    def body(hh, xs):
        p, ck, cv = xs
        hh = runtime.shard_activation(hh)
        hn = L.rmsnorm(hh, p["attn_norm"], cfg.norm_eps)
        a, ck, cv = L.extend_attention(p["attn"], hn, ck, cv, pos, cfg,
                                       window=window or cfg.sliding_window,
                                       block_mask=block_mask,
                                       q_positions=q_positions)
        hh = hh + a
        hn = L.rmsnorm(hh, p["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = MOE.moe_apply(p["moe"], hn, cfg)
        else:
            m = L.mlp_block(p["mlp"], hn, cfg.mlp_activation)
        return hh + m, (ck, cv)

    h, (ks, vs) = jax.lax.scan(body, h, (params["blocks"], cache["k"], cache["v"]))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(_head(params), h)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, {"k": ks, "v": vs, "pos": pos + jnp.asarray(T, jnp.int32)}


def decode_step(params, token, cache, cfg, *, window: int = 0):
    """One decode step. token: (B, 1) int32. Returns (logits (B,V), cache)."""
    h = L.embed(params["embed"], token).astype(_adt(cfg))
    pos = cache["pos"]

    def body(hh, xs):
        p, ck, cv = xs
        hh = runtime.shard_activation(hh)
        hn = L.rmsnorm(hh, p["attn_norm"], cfg.norm_eps)
        a, ck, cv = L.decode_attention(p["attn"], hn, ck, cv, pos, cfg,
                                       window=window or cfg.sliding_window)
        hh = hh + a
        hn = L.rmsnorm(hh, p["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = MOE.moe_apply(p["moe"], hn, cfg)
        else:
            m = L.mlp_block(p["mlp"], hn, cfg.mlp_activation)
        return hh + m, (ck, cv)

    h, (ks, vs) = jax.lax.scan(body, h, (params["blocks"], cache["k"], cache["v"]))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(_head(params), h[:, 0, :])
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, {"k": ks, "v": vs, "pos": pos + 1}
