"""Operations and bytes that the algorithm needs, from shapes.

These count the work of the configured algorithm, not what a compiled
program happens to execute: no pow2 overshoot, no padding, no masked
slots or blocks.  A later change to the program cannot move them.

``cfg`` is a model entry of a configuration file.  What one token costs
through a whole model is its family's count (``token_flops`` and
``attn_flops`` in ``bench/families/<family>.py``); here are the counts
every family shares: contexts, one layer's paged decode attention
(``num_heads``, ``num_kv_heads``, ``head_dim``), the peaks and the
roofline.  Bytes assume bf16 (2 bytes) activations, weights and KV
cache, as the configurations state.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable

from bench import families

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


def prefill_ctx(n: int) -> float:
    """Summed causal context of a prefill of ``n`` tokens."""
    return n * (n + 1) / 2.0


def decode_ctx(start: float, n: float) -> float:
    """Summed context of ``n`` decode steps whose first attends over
    ``start + 1`` keys (its own included)."""
    return n * (start + 1) + n * (n - 1) / 2.0


def forward_flops(cfg: dict, tokens: float, ctx_sum: float) -> float:
    """FLOPs of ``tokens`` tokens through the model whose attended context
    lengths sum to ``ctx_sum``, by the model's family's counts."""
    fam = families.of(cfg)
    return tokens * fam.token_flops(cfg) + fam.attn_flops(cfg, ctx_sum)


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
