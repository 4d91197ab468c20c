#!/usr/bin/env python3
"""Smoke run of the served path on a TPU, at full model width.

    python3 chip_smoke.py             # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4   # four chips: the sharded cloud only

The edge is smollm-135m as published.  The cloud is granite-8b at its
published widths with its depth cut to 16 of 36 layers (about 3.7 B
parameters, 7.4 GB in bf16), so that one 16 GB v5e chip holds both models
and the paged KV.  Weights are random, made from fixed seeds.  Every
serving phase goes through ``BatchedEngine(kv_layout="paged",
clock=WallClock())``:

  (a) edge only (threshold 1.1: nothing escalates);
  (b) every request escalated, linear speculation;
  (c) every request escalated, tree speculation;
  (d) one full-width ``paged_decode_step`` per model with the Pallas
      kernel and with the jnp reference; their logits must agree.

``--chips 4`` runs only the mesh path: granite-8b at its full 36 layers,
tensor-parallel on mesh ``data=1,model=4``, serving escalated requests;
then the 16-layer cloud on that mesh and on device 0 alone, whose prompt
logits and one paged decode step's logits (the sharded kernel path) must
agree.

Compile and wall seconds and ``peak_bytes_in_use`` are set-up facts, not
speed results.  With no TPU the script exits non-zero before any phase:
there is no CPU fallback.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_REQUESTS = 8
PROMPT_LEN = 512
MAX_NEW = 64
CLOUD_LAYERS = 16          # of granite-8b's 36: both models fit one chip
BLOCK_SIZE = 32
# bf16 logit agreement between two computations of the same logits that
# round differently (another attention read, another reduction order):
# ||a - b||_2 / ||b||_2.  Sound readings: 0.0146 (smollm-135m) and 0.0122
# (granite-8b, 16 layers) for the kernel against the reference on a v5e,
# 0.0147 for the 16-layer cloud's prompt logits on four v5e chips against
# one.  The same kernel-vs-reference step at smollm-135m width on the CPU
# (interpret mode) reads 0.0118 sound, and with a fault planted in the
# kernel 0.0313 (the new token's own key masked out), 0.151 (the last
# block masked out), 1.24 (kv heads reversed).  The bound sits between
# the largest sound and the smallest faulty reading.
LOGIT_RTOL = 2e-2
# share of rows whose greedy token agrees: near-flat random-weight logits
# may flip a top-2 pair within the bound above; a wrong read flips most
# rows (0.0 with kv heads reversed)
ARGMAX_FLOOR = 0.75


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_tpu() -> dict:
    info = device_info()
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{info['platform']!r}); this script runs only on the chip")
    return info


class CompileClock:
    """Sums XLA backend compile seconds from JAX's monitoring events."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += secs


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def report(phase: str, **kw):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kw.items()),
          flush=True)


def build_models(cloud_layers: int):
    from repro.configs import get_config
    from repro.models import Model
    e_cfg = get_config("smollm-135m")
    c_cfg = get_config("granite-8b")
    report("config", edge=f"{e_cfg.name} layers={e_cfg.num_layers} "
           f"d={e_cfg.d_model} heads={e_cfg.num_heads}/{e_cfg.num_kv_heads} "
           f"vocab={e_cfg.vocab_size} {e_cfg.param_dtype}",
           cloud=f"{c_cfg.name} d={c_cfg.d_model} "
           f"heads={c_cfg.num_heads}/{c_cfg.num_kv_heads} "
           f"d_ff={c_cfg.d_ff} vocab={c_cfg.vocab_size} {c_cfg.param_dtype}",
           cut=f"cloud depth {cloud_layers} of {c_cfg.num_layers} layers")
    c_cfg = c_cfg.replace(num_layers=cloud_layers)
    assert e_cfg.vocab_size == c_cfg.vocab_size
    return Model(e_cfg), Model(c_cfg)


def init_params(model, seed: int, shardings=None):
    """Weights built on the device by one jitted init (no host copy)."""
    params = jax.jit(model.init, out_shardings=shardings)(
        jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def make_prompts(vocab: int, n: int, length: int, seed: int = 0):
    from repro.data import SyntheticLM
    synth = SyntheticLM(vocab)
    rng = np.random.default_rng(seed)
    return [synth.sample(rng, i % synth.n_domains, length) for i in range(n)]


def has_kernel(hlo: str) -> bool:
    """A compiled Mosaic kernel, not interpret mode or the jnp path."""
    return "tpu_custom_call" in hlo


def tick_hlo(eng, edge_params) -> str:
    """Compiled text of the engine's own jitted edge decode tick, at the
    shapes and placements its drain used.  The paged state is built
    abstractly, so the check allocates nothing on the device."""
    import contextlib

    from repro import runtime
    from repro.launch.sharding import paged_cache_shardings
    B, lane = eng.batch_size, eng.edge
    states = []

    def caches():
        states.append(lane.make_state(edge_params, B, eng._slot_len,
                                      num_blocks=eng.kv_blocks))
        return states[0].caches

    abstract = jax.eval_shape(caches)
    ctx = contextlib.nullcontext()
    if eng.mesh is not None:
        sh = paged_cache_shardings(abstract, eng.mesh, lane.model.cfg,
                                   states[0].data_shards)
        abstract = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), abstract, sh)
        ctx = runtime.mesh_context(eng.mesh)
    with ctx:
        return lane._chunk.lower(
            edge_params, abstract, jnp.zeros((B, 1, 1), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.float32),
            jax.random.PRNGKey(0), jnp.int32(-1),
            n_steps=eng.tick_tokens).compile().as_text()


def serve_phase(phase, edge, cloud, ep, cp, prompts, max_new, *, threshold,
                spec_mode, expect_path, clock, mesh=None):
    """Serve ``prompts`` through a fresh paged ``BatchedEngine`` on the
    wall clock and check every request; returns the token streams."""
    from repro.core.policy import SpeculativePolicy
    from repro.core.scheduler import BatchedEngine
    from repro.core.traffic import WallClock
    c0, t0 = clock.total, time.perf_counter()
    eng = BatchedEngine(edge, cloud, batch_size=N_REQUESTS, gamma=4,
                        temperature=0.0, use_cache=False,
                        policy=SpeculativePolicy(threshold),
                        spec_mode=spec_mode, kv_layout="paged",
                        kv_block_size=BLOCK_SIZE, clock=WallClock(),
                        mesh=mesh)
    traces = eng.serve_batch(ep, cp, prompts, max_new)
    wall = time.perf_counter() - t0
    vocab = edge.cfg.vocab_size
    assert len(traces) == len(prompts), (phase, len(traces))
    for i, tr in enumerate(traces):
        assert tr.path == expect_path, (phase, i, tr.path)
        assert tr.tokens is not None and len(tr.tokens) == max_new, \
            (phase, i, None if tr.tokens is None else len(tr.tokens))
        toks = np.asarray(tr.tokens)
        assert ((toks >= 0) & (toks < vocab)).all(), (phase, i)
        # the trace's uncertainty is the entropy of the edge logits
        assert np.isfinite(tr.uncertainty), (phase, i, tr.uncertainty)
    peak = peak_bytes()
    kernel = has_kernel(tick_hlo(eng, ep))
    assert kernel, f"{phase}: the decode tick holds no Pallas kernel"
    st = eng.stats()
    report(phase, requests=len(prompts), completed=len(traces),
           tokens_each=max_new, path=expect_path,
           spec_mode=st["spec_mode"],
           accepted_tokens_per_step=st["accepted_tokens_per_step"],
           tick_tpu_custom_call=kernel, compile_s=clock.total - c0,
           wall_s=wall, peak_bytes_in_use=peak)
    return [tr.tokens for tr in traces]


def prefill_pool(model, params, prompts, max_new):
    """A paged cache holding ``prompts`` prefilled, one block range per
    sequence (block 0 is the trap), lengths staggered to exercise the
    masks."""
    B, S = len(prompts), len(prompts[0])
    mb = -(-(S + max_new) // BLOCK_SIZE)
    _, c = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                              max_seq=mb * BLOCK_SIZE))(
        params, jnp.asarray(np.stack(prompts), jnp.int32))
    L, _, _, Kv, hd = c["k"].shape

    def pool(x):
        blocks = x.reshape(L, B * mb, BLOCK_SIZE, Kv, hd)
        return jnp.concatenate([jnp.zeros_like(blocks[:, :1]), blocks], 1)

    table = 1 + np.arange(B * mb, dtype=np.int32).reshape(B, mb)
    pos = np.asarray([S - 7 * b for b in range(B)], np.int32)
    return {"k": pool(c["k"]), "v": pool(c["v"]),
            "table": jnp.asarray(table), "pos": jnp.asarray(pos)}


def logit_err(a, b) -> float:
    """Relative L2 distance of logits ``a`` from reference ``b``."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def check_logits(what, got, want):
    """Assert ``got`` finite and within ``LOGIT_RTOL`` and ``ARGMAX_FLOOR``
    of ``want``; returns (relative L2, greedy agreement)."""
    assert np.isfinite(got).all() and np.isfinite(want).all(), what
    err = logit_err(got, want)
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    assert err <= LOGIT_RTOL and agree >= ARGMAX_FLOOR, (what, err, agree)
    return err, agree


def decode_logits(model, params, tok, cache, backend):
    """Logits of one jitted paged decode step, and whether its compiled
    HLO holds the Pallas kernel."""
    step = jax.jit(lambda p, t, c: model.paged_decode_step(
        p, t, c, attn_backend=backend))
    hlo = step.lower(params, tok, cache).compile().as_text()
    logits, _ = step(params, tok, cache)
    return np.asarray(logits, np.float32), has_kernel(hlo)


def decode_parity(phase, model, params, prompts, clock):
    """Phase (d): one full-width paged decode step, Pallas kernel vs jnp
    reference read, on the same prefilled pool."""
    c0, t0 = clock.total, time.perf_counter()
    cache = prefill_pool(model, params, prompts, MAX_NEW)
    tok = jnp.asarray([[p[-1]] for p in prompts], jnp.int32)
    lk, k_call = decode_logits(model, params, tok, cache, "kernel")
    lr, r_call = decode_logits(model, params, tok, cache, "ref")
    assert k_call and not r_call, (phase, k_call, r_call)
    err, agree = check_logits(phase, lk, lr)
    report(phase, model=model.cfg.name, batch=len(prompts),
           logits_rel_l2=err, tolerance=LOGIT_RTOL,
           argmax_agreement=agree, argmax_floor=ARGMAX_FLOOR,
           kernel_tpu_custom_call=k_call,
           compile_s=clock.total - c0, wall_s=time.perf_counter() - t0,
           peak_bytes_in_use=peak_bytes())


def run_one_chip(clock):
    edge, cloud = build_models(CLOUD_LAYERS)
    t0 = time.perf_counter()
    ep = init_params(edge, 0)
    cp = init_params(cloud, 1)
    report("init", edge_params=edge.cfg.n_params(),
           cloud_params=cloud.cfg.n_params(),
           wall_s=time.perf_counter() - t0, peak_bytes_in_use=peak_bytes())
    prompts = make_prompts(edge.cfg.vocab_size, N_REQUESTS, PROMPT_LEN)
    kw = dict(clock=clock)
    serve_phase("a_edge", edge, cloud, ep, cp, prompts, MAX_NEW,
                threshold=1.1, spec_mode="linear", expect_path="edge", **kw)
    serve_phase("b_linear", edge, cloud, ep, cp, prompts, MAX_NEW,
                threshold=-1.0, spec_mode="linear",
                expect_path="speculative", **kw)
    serve_phase("c_tree", edge, cloud, ep, cp, prompts, MAX_NEW,
                threshold=-1.0, spec_mode="tree",
                expect_path="speculative", **kw)
    decode_parity("d_edge", edge, ep, prompts, clock)
    decode_parity("d_cloud", cloud, cp, prompts, clock)


def run_four_chips(clock, n_requests: int = 4, max_new: int = 32):
    """Sharded serving on ``data=1,model=4``: the full-depth cloud, then
    the cut cloud on the mesh against device 0 alone."""
    from repro import runtime
    from repro.launch.mesh import parse_mesh_arg
    from repro.launch.sharding import (paged_cache_shardings,
                                       params_shardings, replicated_shardings)
    from repro.models import Model
    mesh = parse_mesh_arg("data=1,model=4")
    report("mesh", shape=dict(mesh.shape), devices=mesh.size)
    edge, cloud = build_models(CLOUD_LAYERS)
    full = Model(cloud.cfg.replace(num_layers=36))
    prompts = make_prompts(edge.cfg.vocab_size, n_requests, PROMPT_LEN)

    def sharded(model, seed):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(seed))
        return init_params(model, seed,
                           params_shardings(shapes, mesh, model.cfg))

    ep = init_params(edge, 0, replicated_shardings(
        jax.eval_shape(edge.init, jax.random.PRNGKey(0)), mesh))
    fp = sharded(full, 1)
    on_dev0 = sum(s.data.nbytes for x in jax.tree.leaves(fp)
                  for s in x.addressable_shards
                  if s.device == mesh.devices.flat[0])
    report("full_init", cloud_layers=full.cfg.num_layers,
           cloud_params=full.cfg.n_params(), weight_bytes_on_device0=on_dev0)
    serve_phase("mesh_full", edge, full, ep, fp, prompts, max_new,
                threshold=-1.0, spec_mode="linear",
                expect_path="speculative", clock=clock, mesh=mesh)
    del fp

    # the cut cloud: one set of weights, served sharded and on device 0
    cp = init_params(cloud, 1)
    cp_mesh = jax.device_put(cp, params_shardings(cp, mesh, cloud.cfg))
    toks = jnp.asarray(np.stack(prompts), jnp.int32)
    prefill = jax.jit(lambda p, t: cloud.prefill(p, {"tokens": t})[0])
    single = np.asarray(prefill(cp, toks), np.float32)
    with runtime.mesh_context(mesh):
        sharded_lg = np.asarray(
            jax.jit(lambda p, t: cloud.prefill(p, {"tokens": t})[0])(
                cp_mesh, toks), np.float32)
    err, agree = check_logits("mesh vs device 0 prompt logits",
                              sharded_lg, single)
    # one paged decode step on the same prefilled pool: on the mesh the
    # Pallas call runs per shard (kv heads over "model")
    cache = prefill_pool(cloud, cp, prompts, MAX_NEW)
    tok = jnp.asarray([[p[-1]] for p in prompts], jnp.int32)
    one_d, _ = decode_logits(cloud, cp, tok, cache, "kernel")
    cache_mesh = jax.device_put(
        cache, paged_cache_shardings(cache, mesh, cloud.cfg))
    with runtime.mesh_context(mesh):
        mesh_d, d_call = decode_logits(cloud, cp_mesh, tok, cache_mesh,
                                       "kernel")
    assert d_call, "the sharded decode step holds no Pallas kernel"
    d_err, d_agree = check_logits("mesh vs device 0 decode logits",
                                  mesh_d, one_d)
    del cache, cache_mesh
    mesh_toks = serve_phase("mesh_cut", edge, cloud, ep, cp_mesh, prompts,
                            max_new, threshold=-1.0, spec_mode="linear",
                            expect_path="speculative", clock=clock,
                            mesh=mesh)
    ep0 = jax.device_put(ep, jax.devices()[0])
    one_toks = serve_phase("single_cut", edge, cloud, ep0, cp, prompts,
                           max_new, threshold=-1.0, spec_mode="linear",
                           expect_path="speculative", clock=clock)
    same = sum(a == b for x, y in zip(mesh_toks, one_toks)
               for a, b in zip(x, y))
    report("mesh_vs_device0", prompt_logits_rel_l2=err,
           prompt_argmax_agreement=agree, decode_logits_rel_l2=d_err,
           decode_argmax_agreement=d_agree, decode_tpu_custom_call=d_call,
           tolerance=LOGIT_RTOL, argmax_floor=ARGMAX_FLOOR,
           token_agreement=f"{same}/"
           f"{sum(len(x) for x in one_toks)}",
           identical_requests=sum(x == y for x, y in
                                  zip(mesh_toks, one_toks)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a 2x2 host")
    args = ap.parse_args()
    info = require_tpu()
    if info["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {info['count']}")
    from repro.launch.compile_cache import enable_compile_cache
    report("setup", device=json.dumps(info), jax=jax.__version__,
           compile_cache=enable_compile_cache())
    clock = CompileClock()
    if args.chips == 4:
        run_four_chips(clock)
    else:
        run_one_chip(clock)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
