"""The granite-4.0-h hybrid family (``granitemoehybrid`` with no experts,
e.g. granite-4.0-h-micro): each layer is a Mamba2 mixer or grouped-query
attention with no position encoding (NoPE), as ``layer_types`` says,
followed by a SwiGLU MLP, RMSNorm before each, the output head tied to the
embedding, and granite's multipliers: the embedding times
``embedding_multiplier``, attention scores times ``attention_multiplier``
(in place of ``1 / sqrt(head_dim)``), each mixer and MLP output times
``residual_multiplier`` before its residual add, the logits divided by
``logits_scaling``.

The Mamba2 mixer (one group): ``in_proj`` gives the gate ``z``, the conv
input ``xBC`` and ``dt``; a causal depthwise conv of width ``conv_kernel``
with bias, then SiLU, splits into ``x`` (heads of ``ssm_head_dim``), ``B``
and ``C`` (``ssm_state`` each); ``dt = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``; per head ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T`` and
``y_t = h_t C_t + D x_t``; then ``rmsnorm(y * silu(z))`` and ``out_proj``.

Weights, in the program's parameter layout (``models/granite_hybrid.py``):
stacked per kind, ``mamba`` ({norm, mixer: {in_z, in_xbc, in_dt (``in_proj``'s
column blocks), conv: {w, b}, A_log, dt_bias, D, norm, out_proj},
mlp_norm, mlp}) and ``attention`` ({norm,
attn: {wq, wk, wv, wo}, mlp_norm, mlp}), each layer's leaves drawn under
its index in the whole stack; bf16, except ``A_log``, ``dt_bias`` and
``D``, which the program keeps in float32.

Reference departures noted: every RMSNorm scale, the gated one included,
is ``1 + w`` (the parameterisation the benchmark's weights are stored in);
the recurrence is a plain sequential scan over tokens, where the program
runs Mamba2's chunked form with a log-space stabiliser; the reference
applies the attention multiplier to the scores, where the program folds
it into ``wq`` (exact: ``1/64 * sqrt(64)`` is a power of two).
"""
from __future__ import annotations

import functools
import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference import _mm, pack, rmsnorm

# leaf ids: part of the key derivation, never renumber
LEAVES = {"embed": 0, "final_norm": 1,
          "norm": 10, "mlp_norm": 11, "w_gate": 12, "w_up": 13,
          "w_down": 14,
          "wq": 20, "wk": 21, "wv": 22, "wo": 23,
          "in_proj": 30, "conv_w": 31, "conv_b": 32, "A_log": 33,
          "dt_bias": 34, "D": 35, "gate_norm": 36, "out_proj": 37}
# Mamba2's initialisation: A = -U[1, 16], dt log-uniform in [1e-3, 1e-1]
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
# a quarter of dense.py's 0.02: at 0.02 the embedding, times
# ``embedding_multiplier`` 12 and read back through the tied head, outweighs
# every layer, and the model repeats its last token whatever the layers
# compute
EMBED_STD = 0.005


def _dims(cfg: dict):
    di = cfg["ssm_expand"] * cfg["d_model"]
    N, P = cfg["ssm_state"], cfg["ssm_head_dim"]
    return di, N, P, di // P


def layers_of(cfg: dict, kind: str) -> List[int]:
    return [i for i, k in enumerate(cfg["layer_types"]) if k == kind]


# ---------------------------------------------------------------- weights
def layer_shapes(cfg: dict, kind: str) -> dict:
    d, f = cfg["d_model"], cfg["d_ff"]
    out = {"norm": (d,), "mlp_norm": (d,), "w_gate": (d, f), "w_up": (d, f),
           "w_down": (f, d)}
    if kind == "attention":
        H, Kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
        out.update(wq=(d, H * hd), wk=(d, Kv * hd), wv=(d, Kv * hd),
                   wo=(H * hd, d))
    else:
        di, N, P, H = _dims(cfg)
        C = di + 2 * N
        out.update(in_proj=(d, 2 * di + 2 * N + H),
                   conv_w=(cfg["conv_kernel"], C), conv_b=(C,),
                   gate_norm=(di,), out_proj=(di, d))
    return out


def _uniform(key, leaf: int, layer, shape, lo: float, hi: float):
    """U[lo, hi) draws under ``weights._leaf``'s key derivation."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAVES[leaf]), layer)
    return jax.random.uniform(k, shape, jnp.float32, lo, hi)


def layer_params(key, layer, cfg: dict, kind: str,
                 dtype=jnp.bfloat16) -> dict:
    """Layer ``layer``'s leaves (flat dict), drawn from their own keys:
    norm offsets N(0, 0.1), the conv bias too, matrices N(0, 1 / fan_in)
    (the conv's fan-in its width); ``A_log``, ``dt_bias`` as Mamba2
    initialises them, ``D`` 1 + N(0, 0.1)."""
    out = {}
    for name, shape in layer_shapes(cfg, kind).items():
        std = W.NORM_STD if len(shape) == 1 else 1.0 / np.sqrt(shape[0])
        out[name] = W._leaf(key, LEAVES[name], shape, std, dtype, layer)
    if kind == "mamba":
        H = _dims(cfg)[3]
        out["A_log"] = jnp.log(_uniform(key, "A_log", layer, (H,),
                                        *A_RANGE))
        lo, hi = (math.log(x) for x in DT_RANGE)
        dt = jnp.exp(_uniform(key, "dt_bias", layer, (H,), lo, hi))
        out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1
        out["D"] = 1.0 + W._leaf(key, LEAVES["D"], (H,), W.NORM_STD,
                                 jnp.float32, layer)
    return out


def top_params(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The leaves outside the layers: embedding (= tied head), final norm."""
    return {"embed": W._leaf(key, LEAVES["embed"],
                             (cfg["vocab_size"], cfg["d_model"]),
                             EMBED_STD, dtype),
            "final_norm": W._leaf(key, LEAVES["final_norm"],
                                  (cfg["d_model"],), W.NORM_STD, dtype)}


def _stack(key, cfg: dict, kind: str, dtype):
    return jax.vmap(lambda l: layer_params(key, l, cfg, kind, dtype))(
        jnp.asarray(layers_of(cfg, kind), jnp.int32))


def _in_blocks(w, cfg: dict) -> dict:
    """``in_proj``'s column blocks as the program holds them: the gate
    ``z``, the conv input ``xBC``, ``dt``."""
    di, N, _, _ = _dims(cfg)
    a, b = di, 2 * di + 2 * N
    return {"in_z": w[..., :a], "in_xbc": w[..., a:b], "in_dt": w[..., b:]}


def program_params(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The whole model in the program's layout (see the module doc)."""
    m = _stack(key, cfg, "mamba", dtype)
    a = _stack(key, cfg, "attention", dtype)
    mlp = lambda f: {n: f[n] for n in ("w_gate", "w_up", "w_down")}
    mamba = {"norm": m["norm"], "mlp_norm": m["mlp_norm"], "mlp": mlp(m),
             "mixer": {**_in_blocks(m["in_proj"], cfg),
                       "conv": {"w": m["conv_w"], "b": m["conv_b"]},
                       "A_log": m["A_log"], "dt_bias": m["dt_bias"],
                       "D": m["D"], "norm": m["gate_norm"],
                       "out_proj": m["out_proj"]}}
    attn = {"norm": a["norm"], "mlp_norm": a["mlp_norm"], "mlp": mlp(a),
            "attn": {n: a[n] for n in ("wq", "wk", "wv", "wo")}}
    return {"mamba": mamba, "attention": attn, **top_params(key, cfg, dtype)}


def make_on_device(seed: int, index: int, cfg: dict):
    """One jitted call that builds model ``index``'s served weights on the
    device, in bf16 (``A_log``, ``dt_bias`` and ``D`` in float32)."""
    return W.on_device(seed, index, lambda k: program_params(k, cfg))


# ---------------------------------------------------------------- reference
def mamba_mixer(x, p, cfg: dict, fp8: bool):
    """Mamba2 over x (n, S, d), a sequential scan over the tokens."""
    n, S, _ = x.shape
    di, N, P, H = _dims(cfg)
    Wc = cfg["conv_kernel"]
    zxbcdt = _mm(x, p["in_proj"], fp8)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N],
                  zxbcdt[..., 2 * di + 2 * N:])
    xp = jnp.pad(xbc, ((0, 0), (Wc - 1, 0), (0, 0)))
    xbc = sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(Wc)) \
        + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(n, S, H, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # (n, S, H)
    A = -jnp.exp(p["A_log"])

    def step(hs, t):                                          # (n, H, P, N)
        x_t, b_t, c_t, dt_t = t
        hs = jnp.exp(dt_t * A)[..., None, None] * hs \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return hs, jnp.einsum("nhpk,nk->nhp", hs, c_t)

    _, ys = jax.lax.scan(step, jnp.zeros((n, H, P, N), jnp.float32),
                         tuple(t.swapaxes(0, 1) for t in (xs, Bm, Cm, dt)))
    y = ys.swapaxes(0, 1) + p["D"][:, None] * xs
    y = y.reshape(n, S, di) * jax.nn.silu(z)
    return _mm(rmsnorm(y, p["gate_norm"], cfg["norm_eps"]), p["out_proj"],
               fp8)


def attention(x, p, cfg: dict, fp8: bool):
    """Causal grouped-query attention over x (n, S, d), no positions."""
    n, S, _ = x.shape
    H, Kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = _mm(x, p["wq"], fp8).reshape(n, S, H, hd)
    k = _mm(x, p["wk"], fp8).reshape(n, S, Kv, hd)
    v = _mm(x, p["wv"], fp8).reshape(n, S, Kv, hd)
    k = jnp.repeat(k, H // Kv, axis=2)          # head h reads kv head h // G
    v = jnp.repeat(v, H // Kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return _mm(o.reshape(n, S, H * hd), p["wo"], fp8)


def block(h, p, cfg: dict, kind: str, fp8: bool):
    """One layer over h (n, S, d), all float32."""
    eps, rm = cfg["norm_eps"], cfg["residual_multiplier"]
    mix = mamba_mixer if kind == "mamba" else attention
    h = h + rm * mix(rmsnorm(h, p["norm"], eps), p, cfg, fp8)
    x = rmsnorm(h, p["mlp_norm"], eps)
    g = jax.nn.silu(_mm(x, p["w_gate"], fp8)) * _mm(x, p["w_up"], fp8)
    return h + rm * _mm(g, p["w_down"], fp8)


def _items(cfg: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()))


# The weights are drawn in programs of their own: drawn inside the layer's
# program, they double its compile time on the chip.
@functools.partial(jax.jit, static_argnames=("cfg_items", "kind"))
def _draw(key, layer, cfg_items, kind):
    """Layer ``layer``'s leaves in float32."""
    return {k: v.astype(jnp.float32) for k, v in
            layer_params(key, layer, dict(cfg_items), kind).items()}


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _top(key, cfg_items):
    """The embedding and final norm in float32."""
    return {k: v.astype(jnp.float32) for k, v in
            top_params(key, dict(cfg_items)).items()}


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "fp8"))
def _layer(h, p, cfg_items, kind, fp8):
    return block(h, p, dict(cfg_items), kind, fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _head(h, idx, top, cfg_items, fp8):
    """Logits (T, V) at flat positions ``idx`` of h (n, S, d)."""
    cfg = dict(cfg_items)
    x = rmsnorm(h.reshape(-1, h.shape[-1])[idx], top["final_norm"],
                cfg["norm_eps"])
    return _mm(x, top["embed"].T, fp8) / cfg["logits_scaling"]


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(tokens, top, cfg_items):
    return top["embed"][tokens] * dict(cfg_items)["embedding_multiplier"]


def logits_at(cfg: dict, seed: int, index: int,
              seqs: Sequence[np.ndarray], starts: Sequence[int],
              fp8: bool = False, device=None) -> List[np.ndarray]:
    """Reference logits of model ``index`` under run seed ``seed``: for
    each token sequence, the logits at positions ``start - 1 ..
    len - 2``.  Layer by layer over all sequences at once."""
    key = W.model_key(seed, index)
    items = _items(cfg)
    toks, idx, spans = pack(seqs, starts)
    put = (lambda x: jax.device_put(x, device)) if device is not None \
        else jnp.asarray
    with jax.default_matmul_precision("highest"):
        top = _top(key, items)
        h = _embed(put(toks), top, items)
        for layer, kind in enumerate(cfg["layer_types"]):
            h = _layer(h, _draw(key, layer, items, kind), items, kind, fp8)
        lg = np.asarray(_head(h, put(idx), top, items, fp8))
    return [lg[a:a + n] for a, n in spans]


# ---------------------------------------------------------------- work
def _matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through: every layer's mixer and MLP
    projections, and the output head."""
    d, f = cfg["d_model"], cfg["d_ff"]
    H, Kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    di, N, P, Hm = _dims(cfg)
    attn = d * H * hd + 2 * d * Kv * hd + H * hd * d
    mamba = d * (2 * di + 2 * N + Hm) + di * d
    return (len(layers_of(cfg, "attention")) * attn
            + len(layers_of(cfg, "mamba")) * mamba
            + cfg["num_layers"] * 3 * d * f + cfg["vocab_size"] * d)


def ssd_flops(cfg: dict) -> int:
    """FLOPs of one token's Mamba2 recurrence in every Mamba2 layer: the
    conv, the state update ``exp(dt A) h + dt x B^T`` and its read
    ``h C`` (2 per multiply-add)."""
    di, N, P, H = _dims(cfg)
    conv = 2 * cfg["conv_kernel"] * (di + 2 * N)
    return len(layers_of(cfg, "mamba")) * (4 * H * P * N + conv)


def token_flops(cfg: dict) -> int:
    """Matmul FLOPs of one token through the model, output head included,
    plus its recurrence FLOPs; attention over the context is apart."""
    return 2 * _matmul_params(cfg) + ssd_flops(cfg)


def attn_flops(cfg: dict, ctx_sum: float) -> float:
    """Attention FLOPs of the attention layers for query tokens whose
    attended context lengths sum to ``ctx_sum``: QK^T and PV."""
    return 4.0 * len(layers_of(cfg, "attention")) * cfg["num_heads"] \
        * cfg["head_dim"] * ctx_sum


def paged_layers(cfg: dict) -> int:
    """The attention layers run paged decode attention once per step."""
    return len(layers_of(cfg, "attention"))


def weight_bytes(cfg: dict) -> int:
    """bf16 bytes of the weights one decode step reads at the least: the
    matmul weights (``token_flops``'s), output head included."""
    return 2 * _matmul_params(cfg)


def state_bytes(cfg: dict) -> int:
    """float32 bytes of one sequence's recurrent state over every Mamba2
    layer: the state ``h`` (heads x head_dim x ssm_state) and the conv's
    last ``conv_kernel - 1`` inputs."""
    di, N, P, H = _dims(cfg)
    per = H * P * N + (cfg["conv_kernel"] - 1) * (di + 2 * N)
    return 4 * len(layers_of(cfg, "mamba")) * per
