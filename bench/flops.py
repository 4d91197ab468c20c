"""Operations and bytes that the algorithm needs, from shapes.

These count the work of the configured algorithm, not what a compiled
program happens to execute: no pow2 overshoot, no padding, no masked
slots or blocks.  A later change to the program cannot move them.

``cfg`` is a model entry of a configuration file (``d_model``,
``num_heads``, ``num_kv_heads``, ``head_dim``, ``d_ff``, ``vocab_size``,
``num_layers``).  Bytes assume bf16 (2 bytes) activations, weights and
KV cache, as the configurations state.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable

BYTES = 2
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """{"flops": FLOP/s, "hbm_bytes_s": bytes/s} of one chip of this kind;
    a kind missing from ``peaks.json`` is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


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


def prefill_ctx(n: int) -> float:
    """Summed causal context of a prefill of ``n`` tokens."""
    return n * (n + 1) / 2.0


def decode_ctx(start: float, n: float) -> float:
    """Summed context of ``n`` decode steps whose first attends over
    ``start + 1`` keys (its own included)."""
    return n * (start + 1) + n * (n - 1) / 2.0


def forward_flops(cfg: dict, tokens: float, ctx_sum: float) -> float:
    return tokens * token_flops(cfg) + attn_flops(cfg, ctx_sum)


def paged_decode_attn(cfg: dict, lengths: Iterable[float]) -> dict:
    """FLOPs and HBM bytes that one layer's single-token paged decode
    attention needs at live lengths ``lengths`` (one per live sequence):
    read q, each sequence's K and V up to its length, write o."""
    H, Kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    n, ctx = 0, 0.0
    for length in lengths:
        n += 1
        ctx += length
    return {"flops": 4.0 * H * hd * ctx,
            "bytes": BYTES * (2.0 * Kv * hd * ctx + 2.0 * n * H * hd)}


def roofline_time(flops: float, nbytes: float, peak: dict) -> dict:
    """Least time the chip needs for the work, and which bound sets it."""
    tc, tm = flops / peak["flops"], nbytes / peak["hbm_bytes_s"]
    return {"seconds": max(tc, tm), "bound": "compute" if tc >= tm
            else "memory"}
