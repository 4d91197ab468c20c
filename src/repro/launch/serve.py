"""Collaborative serving launcher: edge SLM + cloud LLM behind the batched
continuous-batching scheduler, with the collaboration decision surface
picked by ``--policy`` (a ``core/policy.py::CollabPolicy``).

    PYTHONPATH=src python -m repro.launch.serve --edge smollm-135m \
        --cloud granite-8b --requests 32 --reduced \
        --scheduler batched --batch-size 8 --policy cascade

Shipped policies: ``threshold`` (confidence gate -> cloud regen),
``speculative`` / ``skeleton`` (same gate into token-level mixture / task
division), ``cascade`` (cost-ordered multi-tier cascade), ``bandit``
(UCB/LinUCB online routing learned from completion feedback), ``budget``
(per-request cloud-token budget with SLA classes, degrading to edge-accept
when spent).  ``--escalation`` survives as a deprecated alias mapping onto
the matching policy.

``--scheduler per-request`` runs the legacy one-at-a-time reference loop
(useful for tracing and as the baseline the batched numbers are quoted
against).

Any edge/cloud family pair works — mixed ones included, e.g.::

    PYTHONPATH=src python -m repro.launch.serve --edge mamba2-370m \
        --cloud granite-8b --reduced --threshold -1

Recurrent-state edges (mamba2 "ssm", zamba2 "hybrid", "xlstm") ride the
same batched scheduler and grouped speculative escalation as the KV
families: their rewinds are batched accepted-prefix replays behind the
``SequenceState`` adapters in ``core/seq_state.py``.

KV layout (batched scheduler): ``--kv-layout paged`` (the default via
``auto`` on KV-cache transformer families) backs the slots with a shared
pool of ``--kv-block-size``-token blocks and per-slot block tables
(``core/paged_cache.py``) — per-request cache capacity instead of padding
every slot to the longest request.  Requests sharing a block-aligned
prompt prefix (identical system prompts, retried requests) map the shared
blocks physically — refcounts plus copy-on-write at first divergence — and
``--kv-blocks`` caps the pool: when it runs full the scheduler preempts
the slot holding its reservation longest (KV swapped to a host buffer,
restored bit-for-bit later) instead of deferring forever, so an
overcommitted pool still completes every request.  The default sizes the
pool to the dense worst case.  ``--kv-layout dense`` keeps the padded-slab
layout as the parity oracle.

Speculation lanes (batched scheduler): ``--spec-mode`` picks how a
grouped speculative escalation drafts and verifies —

* ``linear`` (default): the classic gamma-token draft tape; any edge/cloud
  family pair, dense or paged group states.
* ``tree``: each slot drafts a packed token TREE (``--spec-tree-width``
  first-level branches, depth ``--gamma``) expanded top-k level-by-level,
  and the cloud verifies ALL candidate branches in ONE tree-masked pass
  (the Pallas tree-attention kernel on TPU) — the longest target-
  consistent root path is accepted, so one verify can commit several
  tokens down the most probable branch.  Dense-attention families only;
  other families fall back to linear (see ``spec_mode`` in the stats).
* ``self``: self-speculative — the EDGE model's early-exit prefix
  (``--spec-exit-layer`` blocks, default half depth) drafts for its own
  full-depth verify through the shared cache.  No second model, no cloud
  verifier: traces carry ``cloud_passes=0``.

All three lanes are lossless against their verifier (greedy outputs are
bit-identical to decoding the verifier alone); the stats line reports
``spec_accept_rate`` and ``accepted_tokens_per_step`` so the lanes can be
compared on acceptance, and ``benchmarks/bench_serving.py --arm
tree_spec`` quotes req/s across them.

Open-loop traffic (batched scheduler): ``--arrival poisson|bursty`` stops
pretending every request is already waiting at t=0 and instead submits
them at sampled arrival times (``--arrival-rate`` req/s long-run average;
bursty adds on/off bursts at a peak rate) against a deterministic virtual
clock (``core/traffic.py``), so the headline numbers become the
latency-honest ones: p50/p99 TTFT measured from SUBMIT (queueing delay
included), p50/p99 TPOT, and — with ``--slo-ms`` — SLO attainment and
goodput-under-SLO.  ``--prefill-chunk`` caps how many prompt tokens a
single tick may prefill, so a long prompt no longer blocks every decoding
request for its whole prefill (chunked prefill interleaves with decode).

Serve-time adaptation (batched scheduler): ``--adapt distill|lora``
closes the inference/learning loop — every completion's supervision
triple (prompt, discarded edge draft, cloud-corrected continuation, plus
``--adapt-topk`` teacher logits when the wave already paid for the cloud
pass) retires into a bounded ``data/feedback_store.FeedbackStore``, and
every ``--adapt-interval`` completions a ``core/adaptation.py``
``AdaptationLoop`` takes jitted background train steps (forward KD on
the full edge params, or LoRA adapter-only updates on the frozen base)
and hot-swaps the result into live serving between scheduler ticks.  The
swap is a pure pytree swap — same treedef/shapes — so the steady state
stays recompile-free across it (the ``bench_serving.py``
``online_adaptation`` arm asserts this with the compile counter, along
with cloud-token share falling as the edge model improves).
``--adapt-checkpoint PATH`` persists the learned artifact on exit (the
LoRA adapter pytree, or the distilled edge params): restore it with
``training/checkpoint.restore``.  Omitting ``--adapt`` keeps serving
byte-identical to the adaptation-free engine.

Running on a mesh: ``--mesh data,model`` shards the batched scheduler over
the local devices — the cloud verifier runs TENSOR-PARALLEL over the
``model`` axis (params partitioned by ``launch/sharding.py``'s rules),
edge drafts stay DATA-parallel over ``data`` (params replicated, batch
slots and the paged block pool split per data shard), and each grouped
escalation wave crosses the mesh as one all-gather of the draft tape
before the TP verify.  Axis sizes are inferred (near-balanced factors of
``jax.device_count()``, larger trailing: 8 devices -> (2, 4)) or pinned
explicitly: ``--mesh data=2,model=4``.  Per-shard KV pools keep the
single-device per-device byte budget, so total ``kv_capacity_blocks``
scales with the shard count (reported in the stats line).  No
accelerators handy? Simulate: set the flag BEFORE the process starts jax::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.launch.serve --reduced \\
        --scheduler batched --mesh data,model

Omitting ``--mesh`` takes the exact single-device code path (no mesh
context, no collectives in any trace).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.engine import CollaborativeEngine
from repro.core.policy import (POLICIES, ThresholdPolicy, make_policy,
                               policy_from_legacy)
from repro.core.scheduler import BatchedEngine
from repro.core.traffic import (WallClock, bursty_arrivals,
                                poisson_arrivals, replay)
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model


def build_policy(args):
    """Construct the ``CollabPolicy`` named by ``--policy`` (or by the
    deprecated ``--escalation`` alias) from its CLI kwargs."""
    if args.escalation is not None:
        if args.policy is not None:
            raise SystemExit("pass --policy or --escalation, not both")
        pol = policy_from_legacy(args.escalation, args.threshold)
        print(f"--escalation is deprecated; use --policy {pol.name}")
        return pol
    name = args.policy or "speculative"
    if name in ("threshold", "speculative", "skeleton"):
        return make_policy(name, threshold=args.threshold)
    if name == "cascade":
        ts = tuple(float(t) for t in args.cascade_thresholds.split(","))
        return make_policy(name, thresholds=ts)
    if name == "bandit":
        return make_policy(name, kind=args.bandit_kind,
                           cost_weight=args.bandit_cost_weight)
    return make_policy(name, threshold=args.threshold,   # budget
                       tokens_per_request=args.budget_tokens)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--edge", default="smollm-135m")
    ap.add_argument("--cloud", default="granite-8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--policy", default=None, choices=sorted(POLICIES),
                    help="collaboration policy (CollabPolicy); default: "
                         "speculative")
    ap.add_argument("--threshold", type=float, default=0.6,
                    help="uncertainty gate (threshold-family and budget "
                         "policies)")
    ap.add_argument("--cascade-thresholds", default="0.45,0.25",
                    help="comma-separated per-tier acceptance thresholds "
                         "(cascade policy)")
    ap.add_argument("--bandit-kind", default="ucb",
                    choices=["ucb", "linucb"])
    ap.add_argument("--bandit-cost-weight", type=float, default=0.3,
                    help="reward = quality - w * cloud-token share")
    ap.add_argument("--budget-tokens", type=float, default=8.0,
                    help="cloud tokens accrued per admitted request "
                         "(budget policy)")
    ap.add_argument("--spec-mode", default=None,
                    choices=["linear", "tree", "self"],
                    help="speculation lane for grouped speculative "
                         "escalations (batched scheduler): linear draft "
                         "tape, packed token-tree verify, or "
                         "self-speculative early-exit drafting; default: "
                         "linear")
    ap.add_argument("--spec-tree-width", type=int, default=None,
                    help="first-level branches of the draft tree "
                         "(--spec-mode tree); default 2")
    ap.add_argument("--spec-exit-layer", type=int, default=None,
                    help="draft exit layer (--spec-mode self); default: "
                         "half the edge model's depth")
    ap.add_argument("--escalation", default=None,
                    choices=["speculative", "cloud", "skeleton"],
                    help="DEPRECATED: legacy mode name; use --policy")
    ap.add_argument("--scheduler", default="batched",
                    choices=["batched", "per-request"],
                    help="batched continuous-batching scheduler vs the "
                         "legacy one-request-at-a-time reference loop")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="scheduler slots (batched scheduler only)")
    ap.add_argument("--tick-tokens", type=int, default=16,
                    help="decode steps per jitted scheduler tick")
    ap.add_argument("--kv-layout", default="auto",
                    choices=["auto", "paged", "dense"],
                    help="KV cache layout (batched scheduler): paged = "
                         "shared block pool + per-slot block tables; dense "
                         "= slots padded to a common slot_len (the parity "
                         "oracle); auto = paged where the model families "
                         "support it")
    ap.add_argument("--kv-block-size", type=int, default=32,
                    help="tokens per KV block (paged layout)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="total KV pool blocks incl. the trap block (paged "
                         "layout); when the pool runs full the scheduler "
                         "preempts-by-swap (host-staged KV) so every "
                         "request still completes. Default: sized to the "
                         "dense worst case")
    ap.add_argument("--arrival", default="none",
                    choices=["none", "poisson", "bursty"],
                    help="open-loop arrival process (batched scheduler): "
                         "submit requests at sampled times against a "
                         "virtual clock instead of all-at-t=0")
    ap.add_argument("--arrival-rate", type=float, default=50.0,
                    help="long-run average arrival rate, requests/second "
                         "of virtual time")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="TTFT SLO in (virtual) ms; enables SLO "
                         "attainment + goodput-under-SLO reporting and "
                         "feeds deadline-aware policies")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="max prompt tokens prefilled per scheduler tick "
                         "(chunked prefill); 0 disables chunking, default "
                         "= --tick-tokens")
    ap.add_argument("--adapt", default=None, choices=["distill", "lora"],
                    help="serve-time adaptation (batched scheduler): "
                         "capture completion triples into a FeedbackStore "
                         "and hot-swap background-trained edge weights "
                         "(distill = forward KD on full params, lora = "
                         "adapter-only on the frozen base)")
    ap.add_argument("--adapt-interval", type=int, default=16,
                    help="take an adaptation update every this many "
                         "completions (0 = capture-only)")
    ap.add_argument("--adapt-topk", type=int, default=8,
                    help="teacher logits kept per cloud-generated token "
                         "(distill mode; rides the wave's existing "
                         "device pull)")
    ap.add_argument("--adapt-checkpoint", default=None, metavar="PATH",
                    help="persist the learned artifact on exit: the LoRA "
                         "adapter pytree (--adapt lora) or the distilled "
                         "edge params (--adapt distill)")
    ap.add_argument("--mesh", default=None, metavar="AXES",
                    help="shard the batched scheduler over the local "
                         "devices: comma-separated axis names, e.g. "
                         "'data,model' (sizes inferred) or "
                         "'data=2,model=4' (pinned); see the module "
                         "docstring's 'Running on a mesh' section")
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    e_cfg = get_config(args.edge)
    c_cfg = get_config(args.cloud)
    if args.reduced:
        e_cfg, c_cfg = e_cfg.reduced(), c_cfg.reduced()
    # shared vocab required for token-level collaboration
    v = min(e_cfg.vocab_size, c_cfg.vocab_size)
    e_cfg, c_cfg = e_cfg.replace(vocab_size=v), c_cfg.replace(vocab_size=v)

    edge, cloud = Model(e_cfg), Model(c_cfg)
    ep = edge.init(jax.random.PRNGKey(0))
    cp = cloud.init(jax.random.PRNGKey(1))

    synth = SyntheticLM(v)
    rng = np.random.default_rng(0)
    prompts = [synth.sample(rng, i % synth.n_domains, args.prompt_len)
               for i in range(args.requests)]
    paths = {}

    policy = build_policy(args)
    if args.scheduler == "per-request" and not isinstance(policy,
                                                          ThresholdPolicy):
        # serve_reference is the legacy per-token oracle: it cannot honor
        # the assign/decide/feedback hooks, so running it would silently
        # serve speculative@0.6 while reporting this policy's name
        raise SystemExit(
            f"--scheduler per-request only honors the threshold-family "
            f"policies; run --policy {policy.name} on --scheduler batched")
    if args.arrival != "none" and args.scheduler != "batched":
        raise SystemExit("--arrival needs --scheduler batched (the "
                         "per-request loop has no admission queue)")
    if args.mesh is not None and args.scheduler != "batched":
        raise SystemExit("--mesh needs --scheduler batched (the "
                         "per-request loop is single-device)")
    if args.spec_mode not in (None, "linear") \
            and args.scheduler != "batched":
        raise SystemExit("--spec-mode tree/self needs --scheduler batched "
                         "(the per-request loop only drafts linear tapes)")
    if args.adapt is not None and args.scheduler != "batched":
        raise SystemExit("--adapt needs --scheduler batched (capture rides "
                         "the batched scheduler's retirement path)")
    adaptation = None
    if args.adapt is not None:
        from repro.core.adaptation import AdaptationLoop
        adaptation = AdaptationLoop(mode=args.adapt,
                                    interval=args.adapt_interval,
                                    topk=args.adapt_topk)
    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import parse_mesh_arg
        mesh = parse_mesh_arg(args.mesh)
        print(f"mesh: {dict(mesh.shape)} over {jax.device_count()} devices")
    if args.scheduler == "batched":
        eng = BatchedEngine(edge, cloud, batch_size=args.batch_size,
                            gamma=args.gamma, temperature=0.0,
                            policy=policy,
                            tick_tokens=args.tick_tokens,
                            kv_layout=args.kv_layout,
                            kv_block_size=args.kv_block_size,
                            kv_blocks=args.kv_blocks,
                            slo_ms=args.slo_ms,
                            prefill_chunk=args.prefill_chunk,
                            spec_mode=args.spec_mode,
                            spec_tree_width=args.spec_tree_width,
                            spec_exit_layer=args.spec_exit_layer,
                            mesh=mesh, adaptation=adaptation)
        t0 = time.perf_counter()
        if args.arrival != "none":
            gen = (poisson_arrivals if args.arrival == "poisson"
                   else bursty_arrivals)
            at = gen(args.arrival_rate, args.requests, seed=0)
            traces = replay(eng, ep, cp, prompts, args.max_new, at)
        else:
            traces = eng.serve_batch(
                ep, cp, prompts, args.max_new,
                domains=[i % synth.n_domains
                         for i in range(args.requests)])
        dt = time.perf_counter() - t0
        for i, tr in enumerate(traces):
            paths[tr.path] = paths.get(tr.path, 0) + 1
            print(f"req {i:3d} path={tr.path:12s} unc={tr.uncertainty:.3f} "
                  f"edge_calls={tr.edge_calls} cloud_passes={tr.cloud_passes}")
        stats = eng.stats()
    else:
        eng = CollaborativeEngine(edge, cloud, gamma=args.gamma,
                                  temperature=0.0, policy=policy)
        t0 = time.perf_counter()
        for i, prompt in enumerate(prompts):
            tr = eng.serve_reference(ep, cp, prompt, args.max_new)
            paths[tr.path] = paths.get(tr.path, 0) + 1
            print(f"req {i:3d} path={tr.path:12s} unc={tr.uncertainty:.3f} "
                  f"edge_calls={tr.edge_calls} cloud_passes={tr.cloud_passes}")
        dt = time.perf_counter() - t0
        stats = eng.stats()

    toks = args.requests * args.max_new
    print(f"\n{args.requests} requests in {dt:.1f}s "
          f"({args.requests / dt:.2f} req/s, {toks / dt:.1f} tok/s); "
          f"paths: {paths}; cache hit rate {stats['cache_hit_rate']:.2f}")
    print(f"policy: {stats['policy']} "
          + " ".join(f"{k.removeprefix('policy_')}={v}"
                     for k, v in stats.items() if k.startswith("policy_")))
    if stats.get("spec_lanes") and any(
            c["member_rounds"] for c in stats["spec_lanes"].values()):
        print(f"spec: mode={stats['spec_mode']} "
              f"accept_rate={stats['spec_accept_rate']:.2f} "
              f"accepted_tokens_per_step="
              f"{stats['accepted_tokens_per_step']:.2f} "
              + " ".join(f"{m}[draft={c['draft_tokens']} "
                         f"verify={c['verify_tokens']} "
                         f"accepted={c['accepted_tokens']} "
                         f"emitted={c['emitted_tokens']} "
                         f"rounds={c['member_rounds']}]"
                         for m, c in stats["spec_lanes"].items()))
    if "kv_peak_bytes" in stats:
        print(f"kv: layout={stats['kv_layout']} "
              f"peak={stats['kv_peak_bytes'] / 1e6:.2f}MB "
              f"capacity={stats['kv_capacity_bytes'] / 1e6:.2f}MB"
              + (f" blocks_peak={stats['kv_blocks_peak']}"
                 if "kv_blocks_peak" in stats else "")
              + (f" shards={stats['kv_shards']} "
                 f"capacity_blocks={stats['kv_capacity_blocks']}"
                 if stats.get("kv_shards", 1) > 1 else ""))
        if stats.get("kv_prefix_hits") or stats.get("preemptions"):
            print(f"kv: prefix_hits={stats.get('kv_prefix_hits', 0)} "
                  f"shared_blocks={stats.get('kv_shared_blocks', 0)} "
                  f"cow_forks={stats.get('kv_cow_forks', 0)} "
                  f"preemptions={stats.get('preemptions', 0)} "
                  f"swaps={stats.get('kv_swaps', 0)}")
    if "ttft_p50_ms" in stats:
        # VirtualClock latencies are modelled, not measured
        unit = "ms" if isinstance(eng.clock, WallClock) else "virtual ms"
        print(f"latency ({unit}): "
              f"ttft p50={stats['ttft_p50_ms']:.1f} "
              f"p99={stats['ttft_p99_ms']:.1f} "
              f"tpot p50={stats['tpot_p50_ms']:.2f} "
              f"p99={stats['tpot_p99_ms']:.2f} "
              f"makespan={stats['makespan_ms']:.0f} "
              f"(swapped={stats['swapped_requests']} "
              f"deferred={stats['deferred_admissions']})")
        if args.slo_ms is not None:
            print(f"slo: ttft<={args.slo_ms:.0f}ms "
                  f"attainment={stats['slo_attainment']:.2f} "
                  f"goodput={stats['goodput_slo']:.2f} req/s")
    if "adaptation" in stats:
        a = stats["adaptation"]
        loss = "n/a" if a["last_loss"] is None else f"{a['last_loss']:.4f}"
        print(f"adapt: mode={a['mode']} observed={a['observed']} "
              f"updates={a['updates']} steps={a['train_steps']} "
              f"swaps={a['swaps']} loss={loss} "
              f"store={a['store_size']}/{a['store_capacity']} "
              f"(evicted={a['store_evicted']})")
    if args.adapt_checkpoint is not None and adaptation is not None:
        from repro.training import checkpoint
        artifact = adaptation.adapters if args.adapt == "lora" \
            else adaptation.latest
        if artifact is None:
            print(f"adapt: nothing learned yet — skipping checkpoint "
                  f"{args.adapt_checkpoint}")
        else:
            checkpoint.save(args.adapt_checkpoint, artifact,
                            step=adaptation.steps)
            print(f"adapt: saved {args.adapt} artifact to "
                  f"{args.adapt_checkpoint} "
                  f"(restore via training/checkpoint.restore)")


if __name__ == "__main__":
    main()
