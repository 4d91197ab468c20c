"""Granite-4.0-H hybrid decoder (family "granitehybrid", e.g.
``granite-4.0-h-micro``).

Each layer is one mixer, a Mamba2 block or NoPE grouped-query attention as
``cfg.layer_types`` says, followed by a SwiGLU MLP; RMSNorm before each.
Granite's multipliers: the embedding is scaled by
``embedding_multiplier``, attention scores by ``attention_multiplier``
(folded into ``wq`` so that every attention path and the paged kernel keep
their own ``1 / sqrt(head_dim)``), each mixer and MLP output by
``residual_multiplier`` before its residual add, and the tied head's
logits are divided by ``logits_scaling``.

Weights are stacked per kind: ``params["mamba"]`` holds the Mamba2 layers
(``models/ssm.py``'s block under ``mixer``, its input projection held as
the column blocks ``in_z``, ``in_xbc`` and ``in_dt``: at granite's widths
the whole projection's 8512 columns are no multiple of the chip's 128-wide
tile, and the stack would be laid out again on every call),
``params["attention"]`` the attention layers, each with its norms and MLP.  The cache holds the
attention layers' K/V (``k``/``v``: a dense ``(n_attn, B, S, Kv, hd)``
slab, or the paged pool ``(n_attn, NB, bs, Kv, hd)`` with ``table`` and
per-slot ``pos``) beside every Mamba2 layer's recurrent state (``ssm``:
``ssm.mamba2_init_cache`` stacked ``(n_mamba, B, ...)``, float32), its
matrix state stored ``(heads, head_dim, state)`` so that the 128-wide state
axis is the minor one the chip tiles without padding (``_gla_view``).

The layers run in order with one ``fori_loop`` per run of one kind, so the
HLO holds one body per run, not per layer; each body takes its layer's
weights and state by index from its kind's stack and writes the state
back in place.
"""
from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro import runtime
from repro.models import layers as L
from repro.models import ssm

KINDS = ("mamba", "attention")


def _dt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _adt(cfg):
    return jnp.dtype(cfg.activ_dtype)


def kind_runs(layer_types) -> List[Tuple[str, int, int]]:
    """Maximal runs of one kind in layer order, each as (kind, first, end):
    indices into that kind's stack."""
    runs: List[list] = []
    seen = {k: 0 for k in KINDS}
    for k in layer_types:
        if runs and runs[-1][0] == k:
            runs[-1][2] += 1
        else:
            runs.append([k, seen[k], seen[k] + 1])
        seen[k] += 1
    return [tuple(r) for r in runs]


# ----------------------------------------------------------------- init
def split_in_proj(mixer, cfg):
    """A Mamba2 mixer's ``in_proj`` as its column blocks: the gate ``z``,
    the conv input ``xBC`` and ``dt``."""
    di = cfg.ssm_expand * cfg.d_model
    w = mixer.pop("in_proj")
    a, b = di, 2 * di + 2 * cfg.ssm_state
    return {**mixer, "in_z": w[..., :a], "in_xbc": w[..., a:b],
            "in_dt": w[..., b:]}


def init_params(rng, cfg):
    dtype = _dt(cfg)
    d = cfg.d_model
    r = L.split(rng, 3)

    def mamba_layer(k):
        kk = L.split(k, 2)
        return {"norm": jnp.zeros((d,), dtype),
                "mixer": split_in_proj(ssm.init_mamba2(kk[0], cfg, dtype),
                                       cfg),
                "mlp_norm": jnp.zeros((d,), dtype),
                "mlp": L.init_mlp(kk[1], cfg, dtype)}

    def attn_layer(k):
        kk = L.split(k, 2)
        return {"norm": jnp.zeros((d,), dtype),
                "attn": L.init_attention(kk[0], cfg, dtype),
                "mlp_norm": jnp.zeros((d,), dtype),
                "mlp": L.init_mlp(kk[1], cfg, dtype)}

    return {
        "embed": L.init_embedding(r[0], cfg.vocab_size, d, dtype),
        "mamba": jax.vmap(mamba_layer)(
            jax.random.split(r[1], cfg.layers_of("mamba"))),
        "attention": jax.vmap(attn_layer)(
            jax.random.split(r[2], cfg.layers_of("attention"))),
        "final_norm": jnp.zeros((d,), dtype),
    }


# ----------------------------------------------------------------- cache
def _gla_view(s):
    """A Mamba2 state with its matrix state's last two axes swapped: the
    stored ``(..., head_dim, state)`` <-> ``ssm``'s ``(..., state,
    head_dim)``."""
    g = s["gla"]
    return {**s, "gla": g._replace(S=jnp.swapaxes(g.S, -1, -2))}


def init_state(cfg, batch: int):
    """Every Mamba2 layer's zero state for ``batch`` sequences, stacked
    ``(n_mamba, batch, ...)``, float32."""
    n = cfg.layers_of("mamba")
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                        _gla_view(ssm.mamba2_init_cache(cfg, batch)))


def init_cache(cfg, batch: int, max_seq: int):
    shape = (cfg.layers_of("attention"), batch, max_seq, cfg.num_kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, _dt(cfg)), "v": jnp.zeros(shape, _dt(cfg)),
            "pos": jnp.zeros((), jnp.int32), "ssm": init_state(cfg, batch)}


def init_paged_cache(cfg, num_blocks: int, block_size: int, batch: int,
                     max_blocks: int):
    """The attention layers' K/V in one block pool per layer (sized by
    attention-layer count, ``core/paged_cache.py``'s protocol) beside one
    row of Mamba2 state per slot."""
    shape = (cfg.layers_of("attention"), num_blocks, block_size,
             cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, _dt(cfg)), "v": jnp.zeros(shape, _dt(cfg)),
            "table": jnp.zeros((batch, max_blocks), jnp.int32),
            "pos": jnp.zeros((batch,), jnp.int32),
            "ssm": init_state(cfg, batch)}


# ----------------------------------------------------------------- layers
def _at(tree, j):
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, j, keepdims=False), tree)


def _put(tree, sub, j):
    return jax.tree.map(
        lambda x, y: jax.lax.dynamic_update_index_in_dim(
            x, y.astype(x.dtype), j, 0), tree, sub)


def _attn_weights(p, cfg):
    """``wq`` scaled so that ``1 / sqrt(head_dim)`` gives the attention
    multiplier (an exact power of two for granite-4.0-h)."""
    if not cfg.attention_multiplier:
        return p
    s = cfg.attention_multiplier * math.sqrt(cfg.head_dim)
    return {**p, "wq": p["wq"] * jnp.asarray(s, p["wq"].dtype)}


def _residual(h, out, cfg):
    return h + cfg.residual_multiplier * out


def _mlp(p, h, cfg):
    x = L.rmsnorm(h, p["mlp_norm"], cfg.norm_eps)
    return _residual(h, L.mlp_block(p["mlp"], x, cfg.mlp_activation), cfg)


def _run(params, h, cache, cfg, mamba_fn, attn_fn, remat: bool = False):
    """The layers in order: one ``fori_loop`` per run of one kind.
    ``mamba_fn(p, x, state) -> (out, state)`` and ``attn_fn(p, x, k, v) ->
    (out, k, v)`` are the mixers over the normed input; the residual and
    MLP are added here.  Returns (h, cache with the new state and K/V)."""
    st, kp, vp = cache["ssm"], cache["k"], cache["v"]
    eps = cfg.norm_eps

    def mamba_body(j, c):
        hh, st = c
        p, s0 = _at(params["mamba"], j), _at(st, j)
        out, s = mamba_fn(p["mixer"], L.rmsnorm(hh, p["norm"], eps),
                          _gla_view(s0))
        hh = _mlp(p, _residual(hh, out, cfg), cfg)
        return hh, _put(st, _gla_view(s), j)

    def attn_body(j, c):
        hh, kp, vp = c
        p = _at(params["attention"], j)
        out, ck, cv = attn_fn(_attn_weights(p["attn"], cfg),
                              L.rmsnorm(hh, p["norm"], eps),
                              _at(kp, j), _at(vp, j))
        hh = _mlp(p, _residual(hh, out, cfg), cfg)
        return hh, _put(kp, ck, j), _put(vp, cv, j)

    if remat:
        mamba_body, attn_body = (
            jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)
            for f in (mamba_body, attn_body))
    for kind, lo, hi in kind_runs(cfg.layer_types):
        if kind == "mamba":
            h, st = jax.lax.fori_loop(lo, hi, mamba_body, (h, st))
        else:
            h, kp, vp = jax.lax.fori_loop(lo, hi, attn_body, (h, kp, vp))
    return h, {**cache, "ssm": st, "k": kp, "v": vp}


def _embed(params, tokens, cfg):
    h = L.embed(params["embed"], tokens).astype(_adt(cfg))
    return h * jnp.asarray(cfg.embedding_multiplier, h.dtype)


def _logits(params, h, cfg):
    """The tied head's float32 logits over the activations as they are
    (a float32 copy of the 100352-row table each step would cost as much
    as reading it)."""
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head", params["embed"])
    logits = jnp.einsum("...d,vd->...v", h, head.astype(h.dtype),
                        preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


# ----------------------------------------------------------------- entry
def extend_step(params, tokens, cache, cfg, *, valid=None,
                remat: bool = False):
    """Multi-token cached decode over a dense cache (prefill chunks,
    speculative extension).  tokens (B, T) -> (logits (B, T, V), cache).
    ``valid`` (traced int): only the first ``valid`` tokens are real and
    ``pos`` moves on by that many; the padding leaves the Mamba2 state as
    it was and its K/V rows lie past ``pos``, where they are masked and
    written over."""
    pos = cache["pos"]

    def mamba_fn(p, x, s):
        return ssm.mamba2_forward(p, x, cfg, cache=s, valid=valid)

    def attn_fn(p, x, ck, cv):
        return L.extend_attention(p, x, ck, cv, pos, cfg)

    h, cache = _run(params, _embed(params, tokens, cfg), cache, cfg,
                    mamba_fn, attn_fn, remat)
    n = tokens.shape[1] if valid is None else valid
    return _logits(params, h, cfg), {**cache,
                                     "pos": pos + jnp.asarray(n, jnp.int32)}


def prefill(params, tokens, cfg, *, max_seq=None):
    """Run the prompt from a zero cache. Returns (last-token logits, cache)."""
    B, S = tokens.shape
    logits, cache = extend_step(params, tokens,
                                init_cache(cfg, B, max_seq or S), cfg)
    return logits[:, -1], cache


def forward(params, tokens, cfg, *, remat: bool = False,
            collect_hidden: bool = False):
    """Teacher-forced forward: (logits (B, S, V), aux loss 0)."""
    if collect_hidden:
        raise ValueError("collect_hidden is not supported for family "
                         "'granitehybrid'")
    B, S = tokens.shape
    logits, _ = extend_step(params, tokens, init_cache(cfg, B, S), cfg,
                            remat=remat)
    return logits, jnp.float32(0.0)


def decode_step(params, token, cache, cfg):
    """One decode step over a dense cache. token (B, 1) -> (logits (B, V),
    cache)."""
    pos = cache["pos"]

    def mamba_fn(p, x, s):
        return ssm.mamba2_step(p, x, s, cfg)

    def attn_fn(p, x, ck, cv):
        return L.decode_attention(p, x, ck, cv, pos, cfg)

    h, cache = _run(params, _embed(params, token, cfg), cache, cfg,
                    mamba_fn, attn_fn)
    return _logits(params, h[:, 0], cfg), {**cache, "pos": pos + 1}


def paged_decode_step(params, token, cache, cfg, *, attn_backend="auto",
                      live=None):
    """One decode step over the paged cache (``init_paged_cache``): the
    attention layers read their pool through the block table (TPU: the
    Pallas paged kernel; ``attn_backend`` as ``Model.paged_decode_step``),
    every slot's Mamba2 state row advances one token, or, given ``live``
    (B,) bool, only the rows of the slots it marks: the others keep their
    state (a slot whose prefill advances its own row).  token (B, 1) ->
    (logits (B, V), cache)."""
    if attn_backend == "gather":
        logits, cache = paged_extend_step(params, token, cache, cfg)
        return logits[:, 0], cache
    pos, table = cache["pos"], cache["table"]

    def mamba_fn(p, x, s):
        out, new = ssm.mamba2_step(p, x, s, cfg)
        if live is None:
            return out, new
        return out, jax.tree.map(
            lambda a, b: jnp.where(
                live.reshape((-1,) + (1,) * (a.ndim - 1)), a, b), new, s)

    def attn_fn(p, x, ck, cv):
        return L.paged_decode_attention_block(p, x, ck, cv, table, pos, cfg,
                                              backend=attn_backend)

    h, cache = _run(params, _embed(params, token, cfg), cache, cfg,
                    mamba_fn, attn_fn)
    logits = runtime.shard_activation(_logits(params, h[:, 0], cfg))
    return logits, {**cache, "pos": pos + 1}


def paged_extend_step(params, tokens, cache, cfg):
    """Multi-token cached decode over the paged cache. tokens (B, T) ->
    (logits (B, T, V), cache)."""
    pos, table = cache["pos"], cache["table"]

    def mamba_fn(p, x, s):
        return ssm.mamba2_forward(p, x, cfg, cache=s)

    def attn_fn(p, x, ck, cv):
        return L.paged_extend_attention(p, x, ck, cv, table, pos, cfg)

    h, cache = _run(params, _embed(params, tokens, cfg), cache, cfg,
                    mamba_fn, attn_fn)
    logits = runtime.shard_activation(_logits(params, h, cfg))
    return logits, {**cache, "pos": pos + jnp.asarray(tokens.shape[1],
                                                      jnp.int32)}


def replay_step(params, tokens, cache, count, cfg):
    """Accepted-prefix replay over a dense cache (speculative rewind, as
    ``ssm.replay_step``): advance through ``tokens[:, :count]`` only."""
    def body(carry, xs):
        t, tok = xs
        _, nxt = decode_step(params, tok[:, None], carry, cfg)
        return ssm.tree_where(t < count, nxt, carry), None

    T = tokens.shape[1]
    cache, _ = jax.lax.scan(body, cache,
                            (jnp.arange(T, dtype=jnp.int32), tokens.T))
    return cache
