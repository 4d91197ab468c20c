"""Random weights from the run seed, for the program and the reference.

The benchmark makes the weights itself, so that the reference can make
the same ones again without taking anything from the program.  Every leaf
of every layer has its own key, ``fold_in(fold_in(model_key, leaf), layer)``,
so one layer's values can be drawn alone (the reference, layer by layer)
or all layers at once into the stacked ``(L, ...)`` leaves the program
serves from (one jitted call on the device, in bf16).

The leaves follow the llama-style block the configurations describe, in
the program's parameter layout: RMSNorm scales are stored as ``w`` with
the norm multiplying by ``1 + w``, projections as ``(in, out)`` matrices,
and the output head tied to the embedding.  Scales: embedding N(0, 0.02);
each projection N(0, 1 / fan_in); norm offsets N(0, 0.1), so that the
``1 + w`` scale is exercised.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# leaf ids: part of the key derivation, never renumber
_LEAVES = {"embed": 0, "final_norm": 1, "attn_norm": 10, "wq": 11,
           "wk": 12, "wv": 13, "wo": 14, "mlp_norm": 15, "w_gate": 16,
           "w_up": 17, "w_down": 18}
NORM_STD = 0.1
EMBED_STD = 0.02


def run_key(seed: int):
    """A PRNG key from any non-negative integer seed (64-bit included)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def model_key(seed: int, index: int):
    """Key of model ``index`` (0 edge, 1 cloud) under run seed ``seed``."""
    return jax.random.fold_in(run_key(seed), index)


def _leaf(key, name: str, shape, std: float, dtype, layer=None):
    k = jax.random.fold_in(key, _LEAVES[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def layer_shapes(cfg: dict) -> dict:
    d, hd = cfg["d_model"], cfg["head_dim"]
    H, Kv, f = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    return {"attn_norm": (d,), "wq": (d, H * hd), "wk": (d, Kv * hd),
            "wv": (d, Kv * hd), "wo": (H * hd, d), "mlp_norm": (d,),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def layer_params(key, layer, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Layer ``layer``'s leaves (flat dict), drawn from their own keys."""
    return {name: _leaf(key, name, shape, NORM_STD if len(shape) == 1
                        else 1.0 / np.sqrt(shape[0]), dtype, layer)
            for name, shape in layer_shapes(cfg).items()}


def top_params(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The leaves outside the layers: embedding (= tied head), final norm."""
    return {"embed": _leaf(key, "embed", (cfg["vocab_size"], cfg["d_model"]),
                           EMBED_STD, dtype),
            "final_norm": _leaf(key, "final_norm", (cfg["d_model"],),
                                NORM_STD, dtype)}


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
    key = model_key(seed, index)
    fn = jax.jit(lambda k: program_params(k, cfg))
    return jax.block_until_ready(fn(key))
