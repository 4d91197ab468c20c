"""Work the configured algorithm does for each request, placed in time.

An edge-served request's work is split into two phases, each spread
evenly over the interval in which the harness saw it happen (times in ms
from the window's start): the edge prefill of the prompt from admission
to the first token, then its decode steps from the first token to the
last.

``share`` gives the part of a phase inside a window; the FLOP and byte
functions come from ``bench/flops.py``.
"""
from __future__ import annotations

from typing import Iterator, Tuple

from bench import families
from bench import flops as F
from bench.latency import completed


def share(a: float, b: float, t0: float, t1: float) -> float:
    """Part of the interval [a, b] inside [t0, t1] (a point counts whole
    when inside)."""
    if b <= a:
        return 1.0 if t0 <= a <= t1 else 0.0
    return max(0.0, min(b, t1) - max(a, t0)) / (b - a)


def phases(rec: dict) -> Iterator[Tuple[str, float, float, float,
                                        float]]:
    """(model, start_ms, end_ms, tokens, ctx_sum) per phase of one
    finished edge-served request."""
    P = len(rec["prompt"]) - 1            # prompt entries prefilled
    n = len(rec["tokens"])
    yield ("edge", rec["admit_ms"], rec["first_ms"], P, F.prefill_ctx(P))
    yield ("edge", rec["first_ms"], rec["retire_ms"], n, F.decode_ctx(P, n))


def edge_served(run):
    return [r for r in completed(run.records) if not r["escalated"]]


def model_flops(run, t0: float, t1: float) -> float:
    """FLOPs of the finished edge-served requests' work that fell in
    [t0, t1]."""
    tot = 0.0
    for rec in edge_served(run):
        for model, a, b, tok, ctx in phases(rec):
            s = share(a, b, t0, t1)
            if s:
                tot += s * F.forward_flops(run.cfg[model], tok, ctx)
    return tot


def mfu(run):
    """Model FLOPs of the window over window x chips x peak, in %; None
    when no edge-served request finished."""
    if not edge_served(run):
        return None
    peak = F.peaks(run.device_kind)["flops"]
    fl = model_flops(run, 0.0, run.seconds * 1e3)
    return 100.0 * fl / (run.seconds * run.chips * peak)


def edge_decode_attention(run, t0: float, t1: float) -> dict:
    """FLOPs and bytes the edge's single-token decode attention needs, in
    every layer that runs it (its family's ``paged_layers``), for the
    edge-served requests' decode steps inside [t0, t1], at each sequence's
    live length."""
    cfg = run.cfg["edge"]
    fl = by = 0.0
    for rec in edge_served(run):
        P = len(rec["prompt"]) - 1
        n = len(rec["tokens"])
        s = share(rec["first_ms"], rec["retire_ms"], t0, t1)
        if not s or not n:
            continue
        # the steps inside the window, at their mean live length
        steps = s * n
        mean_len = P + 1 + (n - 1) / 2.0
        w = F.paged_decode_attn(cfg, [mean_len])
        fl += steps * w["flops"]
        by += steps * w["bytes"]
    L = families.of(cfg).paged_layers(cfg)
    return {"flops": L * fl, "bytes": L * by}
