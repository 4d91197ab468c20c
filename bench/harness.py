"""The benchmark harness: one cell, one seed, one measured window.

Everything cell-specific is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, each model's
weights, reference and work counts in ``bench/families/<family>.py``
(``bench/families/__init__.py`` gives the contract), its traffic mix in
``bench/traffic/<traffic>.json``, and each metric's reader in
``bench/metrics/<metric>.py`` (a ``read(run) -> float | None``).

A run: check the chips (before the program is imported), turn on the
persistent compilation cache, make both models' weights on the device from
the seed, build one ``BatchedEngine`` and drive it through one warm-up
drain of the mix's shapes and its eager table updates at every count
(set-up ends here), then the measured window (a traced run hands the
engine a ``SpanLog`` for it), then the comparison with the float32
reference that decides ``correct``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import logging
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

from bench import traffic as T

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CONFIG_DIR = BENCH / "configs"
METRIC_DIR = BENCH / "metrics"
CACHE_DIR = ROOT / ".jax_cache"
# a traced run's profile, read and then removed
TRACE_DIR = ROOT / ".bench_trace"
# where a traced run's sub-window lies in the measured window, and its
# longest length: a short steady slice keeps the trace small to read
TRACE_AT = 0.3
TRACE_MAX_S = 6.0
# tokens per block of the engine's paged KV cache
KV_BLOCK = 32
# the correctness sample: served tokens and requests per compared model
SAMPLE_TOKENS = 384
SAMPLE_REQUESTS = 8


class ChipError(SystemExit):
    pass


# ---------------------------------------------------------------- spec
def load_spec(path: Optional[Path] = None) -> dict:
    with open(path or ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(CONFIG_DIR / f"{name}.json") as f:
        return json.load(f)


def cell_metrics(spec: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it, and those with no list whose moved end-to-end metric
    the cell reports."""
    e2e = {m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def reader(name: str):
    path = METRIC_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------- device
def require_chips(n: int) -> dict:
    """The device JAX found, or exit non-zero: no TPU, or fewer chips
    than the cell needs.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise ChipError(f"bench: JAX found no TPU (platform "
                        f"{info['platform']!r}); the benchmark runs only on "
                        "the chip")
    if info["count"] < n:
        raise ChipError(f"bench: the cell needs {n} chips, JAX sees "
                        f"{info['count']}")
    return info


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` (a fixed path: the path is
    part of the cache key).  Every program is kept, however fast it
    compiled, so that a later run loads all of them."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileWatch:
    """Counts backend compiles and persistent-cache loads from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _ev(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.compiles, self.compile_s, self.cache_hits)


class CompileNames(logging.Handler):
    """Names of the programs JAX compiles while active (its
    ``jax_log_compiles`` records), to say what compiled in the window."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names: List[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" ")[1])

    def __enter__(self):
        import jax
        log = logging.getLogger("jax")
        # JAX's own handlers would print every record; only this one hears
        self._prev = (jax.config.jax_log_compiles, log.handlers[:],
                      log.propagate)
        log.handlers[:] = [self]
        log.propagate = False
        jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc):
        import jax
        log = logging.getLogger("jax")
        flag, log.handlers[:], log.propagate = self._prev
        jax.config.update("jax_log_compiles", flag)
        return False


def memory_peak(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


# ---------------------------------------------------------------- tracing
class SubWindowTrace:
    """Starts the profiler ``at_ms`` into the window and stops it
    ``len_ms`` later, from a timer thread, recording both ends on the
    engine's clock."""

    def __init__(self, clock, base_ms: float, at_ms: float, len_ms: float,
                 out_dir: str):
        self.clock, self.out_dir = clock, out_dir
        self.start_at, self.stop_at = base_ms + at_ms, base_ms + at_ms + len_ms
        self.t0 = self.t1 = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        import jax
        while self.clock.now() < self.start_at and not self._stop.is_set():
            time.sleep(0.002)
        self.t0 = self.clock.now()
        jax.profiler.start_trace(self.out_dir)
        while self.clock.now() < self.stop_at and not self._stop.is_set():
            time.sleep(0.002)
        self.t1 = self.clock.now()
        jax.profiler.stop_trace()

    def start(self):
        self._thread.start()

    def finish(self):
        self._stop.set()
        self._thread.join(timeout=300)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")


# ---------------------------------------------------------------- run
class Run:
    """What a metric reader reads: the cell, its configuration and mix,
    the request records of the window (times in ms from the window's
    start), set-up seconds and, in a traced run, the reduced trace, its
    sub-window and the engine's spans of the window (``spans``, a list of
    ``core/spans.py`` ``Span``; None in an untraced run)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _build(cfg: dict, mix: dict, seed: int):
    """Both models' family files (looked up first: a family with no file
    fails before the program is built), the program's models, both
    models' weights from the seed, and the engine.  The same ``family``
    names the program's model family (``ModelConfig.family``)."""
    from repro.core.scheduler import BatchedEngine
    from repro.core.traffic import WallClock
    from repro.configs.base import ModelConfig
    from repro.models import Model
    from bench import families
    from bench.policy import MarkedEscalation
    fe, fc = families.of(cfg["edge"]), families.of(cfg["cloud"])
    edge, cloud = Model(ModelConfig(**cfg["edge"])), \
        Model(ModelConfig(**cfg["cloud"]))
    ep = fe.make_on_device(seed, 0, cfg["edge"])
    cp = fc.make_on_device(seed, 1, cfg["cloud"])
    policy = MarkedEscalation(mix["escalate_action"])
    engine = BatchedEngine(edge, cloud, batch_size=mix["batch"],
                           use_cache=False, clock=WallClock(),
                           kv_layout="paged", kv_block_size=KV_BLOCK,
                           policy=policy, spec_mode=mix["spec_mode"])
    return engine, policy, ep, cp


def warm_table_updates(engine) -> None:
    """Run once, at every index count the window can reach, the eager
    block-table updates that the paged engine makes between its programs:
    ``PagedKV.flush`` (the rows and positions of the slots admitted or
    retired in a tick, up to the batch) and ``PagedKV.prepare_tick`` (an
    entry per block that a slot grows into in a tick, up to the batch
    times the blocks a tick's steps can span).  JAX compiles such an
    update once per index count, so a count first met in the window
    would compile there.  On arrays of the table's shape, not the
    engine's: its state is not touched."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    batch = engine.batch_size
    width = -(-engine._slot_len // KV_BLOCK)
    table = jnp.zeros((batch, width), jnp.int32)
    pos = jnp.zeros((batch,), jnp.int32)
    out = []
    for k in range(1, batch + 1):
        ii = jnp.asarray(list(range(k)), jnp.int32)
        out.append(table.at[ii].set(jnp.asarray(np.zeros((k, width),
                                                          np.int32))))
        out.append(pos.at[ii].set(jnp.asarray([0] * k, jnp.int32)))
    for k in range(1, batch * -(-engine.tick_tokens // KV_BLOCK) + 1):
        out.append(table.at[jnp.asarray([0] * k, jnp.int32),
                            jnp.asarray(list(range(k)), jnp.int32)].set(
            jnp.asarray([0] * k, jnp.int32)))
    jax.block_until_ready(out)


def _submit(engine, policy, reqs, base_ms: Optional[float]):
    """Submit ``reqs``, each due ``at_ms`` after ``base_ms`` (None: due
    at once), and mark the escalated ones."""
    rids = []
    for r in reqs:
        at = None if r["at_ms"] is None else base_ms + r["at_ms"]
        rid = engine.submit(r["prompt"], r["max_new"], at=at)
        if r["escalate"]:
            policy.mark(rid)
        rids.append(rid)
    return rids


def _records(engine, results, reqs, rids, base_ms: float) -> List[dict]:
    """One record per request, times in ms from the window's start (the
    engine's stamps: admission, end of prefill, first token ready on the
    host, retirement).  An escalated request's first token is the wave's
    return (``retire``): only then does the host hold any of its
    tokens."""
    out = []
    for r, rid in zip(reqs, rids):
        ev = engine.events.get(rid, {})
        tr = results.get(rid)
        rec = {"rid": rid, "prompt": r["prompt"], "max_new": r["max_new"],
               "escalated": r["escalate"], "due_ms": r["at_ms"],
               "admit_ms": ev.get("admit_ms"), "retire_ms": ev.get("retire_ms"),
               "prefill_done_ms": ev.get("prefill_done_ms"),
               "first_token_ready_ms": ev.get("first_token_ready_ms"),
               "tokens": None if tr is None or tr.tokens is None
               else list(tr.tokens)}
        for k in ("admit_ms", "retire_ms", "prefill_done_ms",
                  "first_token_ready_ms"):
            if rec[k] is not None:
                rec[k] -= base_ms
        if rec["retire_ms"] is not None:
            rec["first_ms"] = rec["retire_ms"] if r["escalate"] \
                else ev["first_token_ms"] - base_ms
        out.append(rec)
    return out


def _window(engine, policy, params, mix, cfg, seed, seconds, tracer_args):
    """Drive the measured window: the mix's arrivals in one drain.
    Returns (records, the sub-window tracer or None)."""
    clock = engine.clock
    win_ms = seconds * 1e3
    base = clock.now() + 5.0
    tracer = None
    if tracer_args is not None:
        tracer = SubWindowTrace(clock, base, TRACE_AT * win_ms,
                                min(TRACE_MAX_S * 1e3, 0.4 * win_ms),
                                tracer_args)
        tracer.start()
    reqs = T.make_requests(mix, seconds, seed, cfg["edge"]["vocab_size"])
    rids = _submit(engine, policy, reqs, base)
    res = engine.run(*params)
    recs = _records(engine, res, reqs, rids, base)
    if tracer is not None:
        tracer.finish()
        tracer.t0 -= base
        tracer.t1 -= base
    return recs, tracer


class Session:
    """One process's hold on a cell: the chips, the persistent cache, both
    models' weights for a seed, and the one ``BatchedEngine`` every drain
    of the process goes through (its compiled programs live on it)."""

    def __init__(self, name: str, cell: dict, cfg: dict, mix: dict,
                 spec: dict, *, seed: int, t_start: Optional[float] = None):
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.info = require_chips(cell["chips"])
        import jax
        self.name, self.cell, self.cfg, self.mix, self.spec = \
            name, cell, cfg, mix, spec
        self.cache = enable_cache()
        self.watch = CompileWatch()
        sys.path.insert(0, str(ROOT / "src"))
        self.engine, self.policy, ep, cp = _build(cfg, mix, seed)
        self.params = (ep, cp)
        self.devices = jax.devices()[:1]

    def warm(self) -> float:
        """The warm-up drain (``traffic.warmup_requests``) on this engine,
        then its eager block-table updates at every count
        (``warm_table_updates``).  Returns the set-up seconds (process start to its end)."""
        warm = T.warmup_requests(self.mix, self.cfg["edge"]["vocab_size"],
                                 self.engine.tick_tokens)
        _submit(self.engine, self.policy, warm, None)
        self.engine.run(*self.params)
        warm_table_updates(self.engine)
        self.setup_s = time.perf_counter() - self.t_start
        c = self.watch.snapshot()
        print(f"setup: setup_s={self.setup_s:.3f} compiles={c[0]} "
              f"compile_s={c[1]:.3f} cache_loads={c[2]} "
              f"cache_dir={self.cache}", flush=True)
        return self.setup_s

    def reseed(self, seed: int) -> None:
        """Replace both models' weights by those of ``seed`` (same shapes,
        so no compile)."""
        from bench import families
        self.params = None
        gc.collect()
        self.params = tuple(
            families.of(self.cfg[m]).make_on_device(seed, i, self.cfg[m])
            for i, m in enumerate(("edge", "cloud")))

    def measure(self, seed: int, seconds: float, trace: bool,
                control: bool = False) -> dict:
        """One measured window under ``seed``'s traffic, its metrics, then
        (the program's weights freed) the comparison that decides
        ``correct``.  A traced window records the engine's spans; the
        warm-up and untraced windows record none.  Returns the result
        dict (the last stdout line)."""
        engine, policy = self.engine, self.policy
        policy.drafts.clear()
        c0 = self.watch.snapshot()
        tdir = str(TRACE_DIR / f"{self.name}-{seed}") if trace else None
        log = None
        if trace:
            from repro.core.spans import SpanLog
            log = engine.spans = SpanLog()
        with CompileNames() as names:
            recs, tracer = _window(engine, policy, self.params, self.mix,
                                   self.cfg, seed, seconds, tdir)
        engine.spans = None
        c1 = self.watch.snapshot()
        print(f"window: compiles={c1[0] - c0[0]} "
              f"compile_s={c1[1] - c0[1]:.3f} cache_loads={c1[2] - c0[2]} "
              f"programs={sorted(set(names.names))}", flush=True)
        device = {**self.info, "memory_peak_bytes": memory_peak(self.devices)}
        run = Run(name=self.name, cell=self.cell, cfg=self.cfg, mix=self.mix,
                  seconds=seconds, records=recs, setup_s=self.setup_s,
                  chips=len(self.devices),
                  device_kind=self.info["kind"], trace=None,
                  trace_window=None,
                  spans=None if log is None else log.spans)
        out = {}
        if trace:
            from bench import trace as TR
            run.trace = TR.used(TR.load(tdir), len(self.devices))
            shutil.rmtree(tdir, ignore_errors=True)
            run.trace_window = (tracer.t0, tracer.t1)
            summ = TR.summarize(run.trace, (tracer.t1 - tracer.t0) / 1e3)
            device["busy_s"] = summ["busy_s"]
            device["window_s"] = summ["window_s"]
            out["breakdown"] = summ["breakdown"]
        metrics = {}
        kind = "per_layer" if trace else "end_to_end"
        for m in cell_metrics(self.spec, self.name, kind):
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        # ---- free the program's weights, then the reference
        drafts = dict(policy.drafts)
        self.params = None
        gc.collect()
        checks, ctrl = check(run, drafts, seed, control)
        failed = sum(1 for r in recs if r["tokens"] is None)
        result = {"correct": verdict(checks, failed),
                  "attempted": len(recs), "failed": failed,
                  "metrics": metrics, "device": device, **out}
        if ctrl is not None:
            # the control's tokens in the served tokens' place, judged by
            # the same verdict against the same limits
            result["control"] = {"correct": verdict(ctrl, 0),
                                 "checks": ctrl}
        result["checks"] = checks
        self.run = run
        return result


def run_cell(name: str, cell: dict, cfg: dict, mix: dict, spec: dict, *,
             seed: int, seconds: float, trace: bool, control: bool = False,
             t_start: Optional[float] = None) -> dict:
    """One run of one cell: set-up, warm-up, one window, the check."""
    s = Session(name, cell, cfg, mix, spec, seed=seed, t_start=t_start)
    s.warm()
    return s.measure(seed, seconds, trace, control)


# ---------------------------------------------------------------- correct
def verdict(checks: dict, failed: int) -> bool:
    """Correct: every request came back and every compared number is
    within its limit."""
    return failed == 0 and all(c["value"] <= c["limit"]
                               for c in checks.values())


def check(run: Run, drafts: Dict[int, List[int]], seed: int,
          control: bool):
    """The comparison with the float32 reference (``bench/reference.py``):
    edge-served tokens and escalated requests' edge drafts against the edge
    reference, escalated requests' served tokens against the cloud
    reference, on a seeded sample of finished requests with the longest in
    it.  Also: every finished request served exactly its ``max_new``
    tokens.  Returns the program's checks and, with ``control``, the
    float8 control's (its top tokens at the same positions in the served
    tokens' place), else None."""
    import jax

    from bench import reference as R
    limits = run.cfg["limits"]
    done = [r for r in run.records if r["tokens"] is not None]
    short = sum(1 for r in done if len(r["tokens"]) != r["max_new"])
    checks = {"wrong_length": {"value": float(short), "limit": 0.0}}
    ctrl = {} if control else None
    # (model index, config key, check name, [(prompt, tokens)])
    edge_items = [(r["prompt"], r["tokens"]) for r in done
                  if not r["escalated"]]
    edge_items += [(r["prompt"], drafts[r["rid"]]) for r in done
                   if r["escalated"] and r["rid"] in drafts]
    cloud_items = [(r["prompt"], r["tokens"]) for r in done
                   if r["escalated"]]
    dev = jax.devices()[0]          # the reference runs on the first chip
    for idx, key, items in ((0, "edge", edge_items),
                            (1, "cloud", cloud_items)):
        if not items:
            continue
        pick = R.pick_sample([(i, len(t)) for i, (_, t) in
                              enumerate(items)], seed, SAMPLE_TOKENS,
                             SAMPLE_REQUESTS, stream=7 + idx)
        got = R.compare(run.cfg[key], seed, idx,
                        [items[i][0] for i in pick],
                        [items[i][1] for i in pick], control=control,
                        device=dev)
        checks[f"{key}_gap"] = {"value": got["gap"],
                                "limit": limits[f"{key}_gap"]}
        if control:
            ctrl[f"{key}_gap"] = {"value": got["control_gap"],
                                  "limit": limits[f"{key}_gap"]}
        print(f"compared: {key} requests={len(pick)} "
              f"tokens={got['tokens']}", flush=True)
    return checks, ctrl


def report(result: dict) -> None:
    """Compared numbers beside their limits as the last lines of stderr
    (the control's first, when read), then the result as the last line
    of stdout."""
    if "control" in result:
        print(f"control correct: {result['control']['correct']}",
              file=sys.stderr, flush=True)
        for k, c in result["control"]["checks"].items():
            print(f"control check {k}: {c['value']!r} limit "
                  f"{c['limit']!r}", file=sys.stderr, flush=True)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)

