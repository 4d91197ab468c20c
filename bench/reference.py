"""Plain float32 reference forward for the llama-style pair, and the
comparison that decides ``correct``.

Written from the published block (RMSNorm, rotary positions on the
halves of each head, grouped-query causal attention, SwiGLU, tied output
head), in straightforward ``jax.numpy`` at float32 under
``default_matmul_precision("highest")``; it imports nothing of the
program.  The weights are drawn again from the run seed by
``bench/weights.py``, one layer at a time, so the 16- or 36-layer cloud
fits beside nothing else once the program's state is freed.

Departure noted: the RMSNorm scale is ``1 + w`` (the parameterisation the
benchmark's weights are stored in), and rotary frequencies use
``theta ** (2i / head_dim)`` with the rotation applied to the two halves
of each head, as in the reference llama implementation.

The compared number is, over the served tokens of a sample of finished
requests, the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.  Greedy serving in
the program's precision puts that gap at the size of its rounding; a
path that computes the wrong thing puts it at the size of the logits'
spread.  The control runs the same forward with every matmul input
quantised to float8 e4m3 (per-row activation and per-column weight
scales: the precision below the configuration's bfloat16) and reads the
reference gap of the token it ranks first.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return x @ w


def rmsnorm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def rope(x, pos, theta):
    """x (B, S, N, hd); pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(h, p, cfg: dict, fp8: bool):
    """One decoder layer over h (B, S, d), all float32."""
    B, S, _ = h.shape
    H, Kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(S)
    x = rmsnorm(h, p["attn_norm"], eps)
    q = rope(_mm(x, p["wq"], fp8).reshape(B, S, H, hd), pos, theta)
    k = rope(_mm(x, p["wk"], fp8).reshape(B, S, Kv, hd), pos, theta)
    v = _mm(x, p["wv"], fp8).reshape(B, S, Kv, hd)
    k = jnp.repeat(k, H // Kv, axis=2)          # head h reads kv head h // G
    v = jnp.repeat(v, H // Kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    h = h + _mm(o.reshape(B, S, H * hd), p["wo"], fp8)
    x = rmsnorm(h, p["mlp_norm"], eps)
    g = jax.nn.silu(_mm(x, p["w_gate"], fp8)) * _mm(x, p["w_up"], fp8)
    return h + _mm(g, p["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _layer(h, key, layer, cfg_items, fp8):
    cfg = dict(cfg_items)
    p = {k: v.astype(jnp.float32)
         for k, v in W.layer_params(key, layer, cfg).items()}
    return block(h, p, cfg, fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _head(h, idx, key, cfg_items, fp8):
    """Logits (T, V) at flat positions ``idx`` of h (B, S, d)."""
    cfg = dict(cfg_items)
    top = {k: v.astype(jnp.float32) for k, v in
           W.top_params(key, cfg).items()}
    x = rmsnorm(h.reshape(-1, h.shape[-1])[idx], top["final_norm"],
                cfg["norm_eps"])
    return _mm(x, top["embed"].T, fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(tokens, key, cfg_items):
    cfg = dict(cfg_items)
    return W.top_params(key, cfg)["embed"].astype(jnp.float32)[tokens]


def _pad_len(n: int, step: int = 128) -> int:
    return -(-n // step) * step


def logits_at(cfg: dict, seed: int, index: int,
              seqs: Sequence[np.ndarray], starts: Sequence[int],
              fp8: bool = False, device=None) -> List[np.ndarray]:
    """Reference logits of model ``index`` under run seed ``seed``: for
    each token sequence, the logits at positions ``start - 1 ..
    len - 2`` (the positions that predict tokens ``start ..``).
    Layer by layer over all sequences at once, padded at the end (the
    mask is causal, so padding changes no earlier position)."""
    key = W.model_key(seed, index)
    items = tuple(sorted(cfg.items()))
    S = _pad_len(max(len(s) for s in seqs))
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    idx, spans = [], []
    for i, (s, st) in enumerate(zip(seqs, starts)):
        spans.append((len(idx), len(s) - st))
        idx.extend(i * S + j for j in range(st - 1, len(s) - 1))
    put = (lambda x: jax.device_put(x, device)) if device is not None \
        else jnp.asarray
    with jax.default_matmul_precision("highest"):
        h = _embed(put(toks), key, items)
        for layer in range(cfg["num_layers"]):
            h = _layer(h, key, layer, items, fp8)
        lg = np.asarray(_head(h, put(np.asarray(idx, np.int32)), key,
                              items, fp8))
    return [lg[a:a + n] for a, n in spans]


def widest_gap(ref_logits: Sequence[np.ndarray],
               tokens: Sequence[Sequence[int]]) -> float:
    """Widest gap, over every compared position, between the reference's
    best logit and its logit of the token compared there."""
    worst = 0.0
    for lg, tk in zip(ref_logits, tokens):
        if len(tk) == 0:
            continue
        tk = np.asarray(tk, np.int64)
        gap = lg.max(-1) - lg[np.arange(len(tk)), tk]
        worst = max(worst, float(gap.max()))
    return worst


def compare(cfg: dict, seed: int, index: int, prompts, served,
            control: bool = False, device=None) -> dict:
    """The widest gap of the ``served`` tokens after each prompt against
    the float32 reference; with ``control`` also the gap of the tokens
    the float8 forward ranks first at the same positions."""
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)])
            for p, t in zip(prompts, served)]
    starts = [len(p) for p in prompts]
    ref = logits_at(cfg, seed, index, seqs, starts, device=device)
    out = {"gap": widest_gap(ref, served),
           "tokens": int(sum(len(t) for t in served))}
    if control:
        low = logits_at(cfg, seed, index, seqs, starts, fp8=True,
                        device=device)
        out["control_gap"] = widest_gap(ref, [l.argmax(-1) for l in low])
    return out


def pick_sample(finished: Sequence[tuple], seed: int, target_tokens: int,
                max_requests: int, stream: int = 7) -> List[int]:
    """Indices into ``finished`` ((key, served_token_count) pairs): the
    longest request, then others drawn from the seed until the sample
    holds ``target_tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -finished[i][1])
    pick = [order[0]]
    rng = np.random.default_rng([int(seed), stream])
    rest = [order[i] for i in rng.permutation(len(order) - 1) + 1]
    total = finished[order[0]][1]
    for i in rest:
        if total >= target_tokens or len(pick) >= max_requests:
            break
        pick.append(i)
        total += finished[i][1]
    return pick

