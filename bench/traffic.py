"""Traffic generation: one general generator driven by a mix file.

A mix (``bench/traffic/<mix>.json``) holds parameters only: the arrival
process and its rate, the prompt and output length distributions, the
escalation share and the engine knobs the mix owns (batch size,
speculation mode).  Every seed gets the SAME request sizes and
inter-arrival gaps in the same order: the sizes are a fixed quantile grid
of the length distributions, and the mix's own ``order_seed`` decides
which request gets which size and gap.  The run seed draws the prompt
tokens (and the weights).  So two seeds do the same work at the same
times, and every run compiles the same shapes.

The arrival arithmetic is a copy of the program's
``core/traffic.py::poisson_arrivals`` (exponential gaps at ``1e3 / rate``
ms), taken at quantiles instead of i.i.d. draws.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of run seed ``seed`` (any
    non-negative integer, 64-bit seeds included)."""
    return np.random.default_rng([int(seed), int(stream)])


def quantile_lengths(dist: dict, k: int) -> np.ndarray:
    """``k`` lengths at the mid-quantiles (i + 0.5) / k of a lognormal
    with the given median and sigma, rounded and clipped to
    [min, max]."""
    if dist.get("kind", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {dist.get('kind')!r}")
    nd = NormalDist()
    q = [(i + 0.5) / k for i in range(k)]
    x = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(p)) for p in q]
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def size_pairs(mix: dict) -> List[tuple]:
    """The mix's fixed multiset of (prompt_len, max_new) pairs: ``sizes``
    quantile points of each distribution, paired by a permutation drawn
    from the mix's own ``pairing_seed`` (not the run seed)."""
    k = mix["sizes"]
    p = quantile_lengths(mix["prompt"], k)
    m = quantile_lengths(mix["max_new"], k)
    perm = np.random.default_rng(mix["pairing_seed"]).permutation(k)
    return [(int(a), int(b)) for a, b in zip(p, m[perm])]


def exp_quantile_gaps(n: int, mean_ms: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at mid-quantiles, mean
    ``mean_ms`` exactly (the grid's own mean is rescaled)."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (mean_ms / g.mean())


def poisson_schedule(rate: float, seconds: float, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    """Open-loop Poisson arrival times (ms) of ``n`` requests over a
    ``seconds`` window: the gap multiset of a Poisson process at
    ``n / seconds`` req/s, in seeded order; the first arrives one gap
    after the window opens, the last as it closes."""
    gaps = exp_quantile_gaps(n, seconds * 1e3 / n)
    return np.cumsum(rng.permutation(gaps))


def requests_in_window(mix: dict, seconds: float) -> int:
    """How many requests a window of ``seconds`` holds: ``rate * seconds``
    rounded."""
    return max(1, round(mix["rate_rps"] * seconds))


def window_sizes(mix: dict, n: int) -> List[tuple]:
    """The (prompt_len, max_new) pairs of ``n`` requests: whole copies of
    the mix's multiset, then a fixed part of one more (chosen by the mix's
    ``pairing_seed``, not the run's).  The same for every seed; the
    largest pair is always in, so every window has the same slot size."""
    pairs = size_pairs(mix)
    k = len(pairs)
    big = max(range(k), key=lambda i: sum(pairs[i]))
    rest = [i for i in np.random.default_rng(mix["pairing_seed"] + 1)
            .permutation(k) if i != big]
    part = sorted([big] + rest[:n % k - 1]) if n % k else []
    return pairs * (n // k) + [pairs[i] for i in part]


def prompt_tokens(rng: np.random.Generator, length: int,
                  vocab: int) -> np.ndarray:
    """A prompt of ``length`` token ids drawn uniformly from the vocab."""
    return rng.integers(0, vocab, size=length, dtype=np.int64).astype(
        np.int32)


def make_requests(mix: dict, seconds: float, seed: int,
                  vocab: int) -> List[Dict]:
    """The requests of one window, in arrival order: dicts with
    ``prompt``, ``max_new``, ``at_ms`` and ``escalate``.  Which size
    arrives after which gap is the mix's own (``order_seed``), the same
    for every run; the run seed draws the prompts' tokens and which
    requests escalate."""
    n = requests_in_window(mix, seconds)
    rng = rng_for(seed, 0)
    order = rng_for(mix["order_seed"], 1)
    fixed = window_sizes(mix, n)
    sizes = [fixed[i] for i in order.permutation(n)]
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    at = poisson_schedule(mix["rate_rps"], seconds, n, order)
    n_esc = round(mix["escalate_share"] * n)
    esc = np.zeros(n, bool)
    esc[rng.permutation(n)[:n_esc]] = True
    return [{"prompt": prompt_tokens(rng, p, vocab), "max_new": m,
             "at_ms": float(t), "escalate": bool(e)}
            for (p, m), t, e in zip(sizes, at, esc)]


def tick_buckets(tick: int) -> List[int]:
    """The step counts a decode tick can run: the powers of two up to the
    engine's tick length (``seq_state.pow2_steps``)."""
    out, p = [], 1
    while p < tick:
        out.append(p)
        p *= 2
    return out + [tick]


def warmup_requests(mix: dict, vocab: int, tick: int) -> List[Dict]:
    """The warm-up drain, due at once.  It holds one request per size of
    the mix's multiset, so every prefill shape the window uses compiles
    (a prompt's chunked prefill compiles per block count).  The largest
    pair keeps its output length, so the drain's slot, and with it every
    decode-state shape, is the window's.  Every other request asks for
    a few tokens, cycling through the tick's step-count buckets (the
    ticks run at least once at each), not for tokens no request needs.
    Its prompts come from a fixed seed, apart from any run's."""
    rng = rng_for(0, 1 << 20)
    share = mix["escalate_share"]
    pairs = size_pairs(mix)
    big = max(range(len(pairs)), key=lambda i: sum(pairs[i]))
    buckets = tick_buckets(tick)
    out = []
    for i, (p, m) in enumerate(pairs):
        if i != big:
            m = min(m, buckets[i % len(buckets)])
        # a mixed mix warms both paths: every other size escalates
        out.append({"prompt": prompt_tokens(rng, p, vocab), "max_new": m,
                    "at_ms": None,
                    "escalate": share >= 1 or (share > 0 and i % 2 == 0)})
    return out
