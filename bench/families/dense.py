"""The dense llama-style family: every layer is grouped-query causal
attention with rotary positions plus a SwiGLU MLP, RMSNorm before each,
and the output head tied to the embedding (smollm-135m, granite-8b).

Weights, in the program's parameter layout: RMSNorm scales stored as
``w`` with the norm multiplying by ``1 + w``, projections as ``(in,
out)`` matrices, stacked ``(L, ...)`` over the layers.

Reference departures noted: the RMSNorm scale is ``1 + w`` (the
parameterisation the benchmark's weights are stored in), and rotary
frequencies use ``theta ** (2i / head_dim)`` with the rotation applied to
the two halves of each head, as in the reference llama implementation.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference import _mm, pack, rmsnorm, rope

# leaf ids: part of the key derivation, never renumber
LEAVES = {"embed": 0, "final_norm": 1, "attn_norm": 10, "wq": 11,
          "wk": 12, "wv": 13, "wo": 14, "mlp_norm": 15, "w_gate": 16,
          "w_up": 17, "w_down": 18}


# ---------------------------------------------------------------- weights
def layer_shapes(cfg: dict) -> dict:
    d, hd = cfg["d_model"], cfg["head_dim"]
    H, Kv, f = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    return {"attn_norm": (d,), "wq": (d, H * hd), "wk": (d, Kv * hd),
            "wv": (d, Kv * hd), "wo": (H * hd, d), "mlp_norm": (d,),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def layer_params(key, layer, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Layer ``layer``'s leaves (flat dict), drawn from their own keys."""
    return {name: W._leaf(key, LEAVES[name], shape,
                          W.NORM_STD if len(shape) == 1
                          else 1.0 / np.sqrt(shape[0]), dtype, layer)
            for name, shape in layer_shapes(cfg).items()}


def top_params(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The leaves outside the layers: embedding (= tied head), final norm."""
    return {"embed": W._leaf(key, LEAVES["embed"],
                             (cfg["vocab_size"], cfg["d_model"]),
                             W.EMBED_STD, dtype),
            "final_norm": W._leaf(key, LEAVES["final_norm"],
                                  (cfg["d_model"],), W.NORM_STD, dtype)}


def program_params(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The whole model in the program's layout: stacked ``(L, ...)``
    block leaves under ``blocks`` ({attn_norm, attn: {wq, wk, wv, wo},
    mlp_norm, mlp: {w_gate, w_up, w_down}}), ``embed``, ``final_norm``."""
    flat = jax.vmap(lambda l: layer_params(key, l, cfg, dtype))(
        jnp.arange(cfg["num_layers"]))
    blocks = {"attn_norm": flat["attn_norm"],
              "attn": {n: flat[n] for n in ("wq", "wk", "wv", "wo")},
              "mlp_norm": flat["mlp_norm"],
              "mlp": {n: flat[n] for n in ("w_gate", "w_up", "w_down")}}
    return {"blocks": blocks, **top_params(key, cfg, dtype)}


def make_on_device(seed: int, index: int, cfg: dict):
    """One jitted call that builds model ``index``'s served weights on the
    device, in bf16."""
    return W.on_device(seed, index, lambda k: program_params(k, cfg))


# ---------------------------------------------------------------- reference
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
         for k, v in layer_params(key, layer, cfg).items()}
    return block(h, p, cfg, fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _head(h, idx, key, cfg_items, fp8):
    """Logits (T, V) at flat positions ``idx`` of h (B, S, d)."""
    cfg = dict(cfg_items)
    top = {k: v.astype(jnp.float32) for k, v in
           top_params(key, cfg).items()}
    x = rmsnorm(h.reshape(-1, h.shape[-1])[idx], top["final_norm"],
                cfg["norm_eps"])
    return _mm(x, top["embed"].T, fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(tokens, key, cfg_items):
    cfg = dict(cfg_items)
    return top_params(key, cfg)["embed"].astype(jnp.float32)[tokens]


def logits_at(cfg: dict, seed: int, index: int,
              seqs: Sequence[np.ndarray], starts: Sequence[int],
              fp8: bool = False, device=None) -> List[np.ndarray]:
    """Reference logits of model ``index`` under run seed ``seed``: for
    each token sequence, the logits at positions ``start - 1 ..
    len - 2`` (the positions that predict tokens ``start ..``).
    Layer by layer over all sequences at once (``reference.pack``)."""
    key = W.model_key(seed, index)
    items = tuple(sorted(cfg.items()))
    toks, idx, spans = pack(seqs, starts)
    put = (lambda x: jax.device_put(x, device)) if device is not None \
        else jnp.asarray
    with jax.default_matmul_precision("highest"):
        h = _embed(put(toks), key, items)
        for layer in range(cfg["num_layers"]):
            h = _layer(h, key, layer, items, fp8)
        lg = np.asarray(_head(h, put(idx), key, items, fp8))
    return [lg[a:a + n] for a, n in spans]


# ---------------------------------------------------------------- work
def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    H, Kv = cfg["num_heads"], cfg["num_kv_heads"]
    return d * H * hd + 2 * d * Kv * hd + H * hd * d + 3 * d * cfg["d_ff"]


def token_flops(cfg: dict) -> int:
    """Matmul FLOPs of one token through the model, output head included
    (2 per multiply-add); attention over the context is apart."""
    return 2 * (cfg["num_layers"] * layer_matmul_params(cfg)
                + cfg["vocab_size"] * cfg["d_model"])


def attn_flops(cfg: dict, ctx_sum: float) -> float:
    """Attention FLOPs of all layers for query tokens whose attended
    context lengths sum to ``ctx_sum``: QK^T and PV, 2 FLOPs each per
    (query head, key, head dim)."""
    return 4.0 * cfg["num_layers"] * cfg["num_heads"] * cfg["head_dim"] \
        * ctx_sum


def paged_layers(cfg: dict) -> int:
    """Every layer runs paged decode attention once per decode step."""
    return cfg["num_layers"]
