"""The comparison that decides ``correct``, and the parts every family's
plain float32 reference forward is built from.

Each model family's reference (``logits_at`` in
``bench/families/<family>.py``) is written from its published block in
straightforward ``jax.numpy`` at float32 under
``default_matmul_precision("highest")``, imports nothing of the program,
and draws its weights again from the run seed (``bench/weights.py``), one
layer at a time, so that it fits beside nothing else once the program's
state is freed.  Here: the shared pieces (RMSNorm on a ``1 + w`` scale,
rotary positions on the two halves of each head, matmuls with the
control's float8 rounding, the batch layout) and the comparison.

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

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import families

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


def pack(seqs: Sequence[np.ndarray], starts: Sequence[int]):
    """Token sequences as one batch for a reference forward, padded at the
    end to a multiple of 128 (the mask is causal, so padding changes no
    earlier position).  Returns the ``(n, S)`` int32 tokens, the flat
    positions ``i * S + start - 1 .. i * S + len - 2`` of each sequence
    (those that predict its tokens ``start ..``), and each sequence's
    ``(offset, count)`` among them."""
    S = _pad_len(max(len(s) for s in seqs))
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    idx, spans = [], []
    for i, (s, st) in enumerate(zip(seqs, starts)):
        spans.append((len(idx), len(s) - st))
        idx.extend(i * S + j for j in range(st - 1, len(s) - 1))
    return toks, np.asarray(idx, np.int32), spans


def _pad_len(n: int, step: int = 128) -> int:
    return -(-n // step) * step


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
    fam = families.of(cfg)
    ref = fam.logits_at(cfg, seed, index, seqs, starts, device=device)
    out = {"gap": widest_gap(ref, served),
           "tokens": int(sum(len(t) for t in served))}
    if control:
        low = fam.logits_at(cfg, seed, index, seqs, starts, fp8=True,
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

