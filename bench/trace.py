"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict (``normalize``), which is also what the recorded test trace
holds:

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": {thread: [[name, start_ns, dur_ns], ...]}}

``ops`` are the device's "XLA Ops" line (one event per executed HLO op or
kernel), ``modules`` its "XLA Modules" line (one event per executed
jitted program), ``host`` the events of each host thread (the Python
tracer's frames and the runtime's own annotations).  Device event names
are cut to the HLO instruction's name (the text before `` = ``).

Everything else here is arithmetic over those lists: the union of busy
intervals, time per name, and the longest idle gaps named by what the
host was doing.
"""
from __future__ import annotations

import bisect
import glob
import re
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[float, float]

# a Mosaic custom call's text names its kernel in its backend config
KERNEL_NAME = re.compile(r'custom-call.*?(?:kernel_name|"name")\\*"?\s*[:=]\s*\\*"([\w.\-]+)')
CONTAINER = re.compile(r"(while|conditional|call)(\.\d+)?$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def normalize(profile) -> dict:
    """A ``jax.profiler.ProfileData`` as plain lists (see module doc)."""
    out = {"devices": {}, "host": {}}
    for plane in profile.planes:
        name = plane.name
        if name.startswith("/device:") and "TPU" in name \
                and "NON_CORE" not in name.upper():
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"xla ops": "ops", "xla modules": "modules"}.get(
                    line.name.strip().lower())
                if key is None:
                    continue
                dev[key].extend([short(e.name), float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events)
            out["devices"][name] = dev
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"].setdefault(line.name, []).extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events)
    return out


def short(name: str) -> str:
    """An HLO op event's name without its full instruction text; a custom
    call (a Pallas kernel) keeps the name of the kernel it runs."""
    op = name.split(" = ", 1)[0].lstrip("%")
    kern = KERNEL_NAME.search(name)
    return f"{op}:{kern.group(1)}" if kern else op


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    return normalize(ProfileData.from_file(find_xplane(trace_dir)))


# ---------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def spans(events, pattern=None) -> List[Interval]:
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    return [(s, s + d) for n, s, d in events if rx is None or rx.search(n)]


# ---------------------------------------------------------------- readings
def busy_ns(dev: dict) -> float:
    """Nanoseconds in which some op ran on this device."""
    return total(union(spans(dev["ops"])))


def time_by_name(events) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for n, _, d in events:
        out[n] = out.get(n, 0.0) + d
    return out


def matching(events, pattern: str) -> Tuple[float, int]:
    """(summed ns, count) of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    ns, k = 0.0, 0
    for n, _, d in events:
        if rx.search(n):
            ns += d
            k += 1
    return ns, k


def main_thread(host: Dict[str, list]) -> list:
    """The host thread with the most events: the one driving the engine
    (the benchmark's own timer thread wakes far less often)."""
    return max(host.values(), key=len) if host else []


def idle_gaps(dev: dict, host: list, top: int = 10,
              min_ns: float = 0.0) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps between busy intervals of one device, each
    named by the host event (of one thread's ``host`` list) that overlaps
    it most among those no longer than four times the gap (the innermost
    activity, not the whole run), in seconds."""
    busy = union(spans(dev["ops"]))
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] - busy[i][1] > min_ns]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    hs = sorted(host, key=lambda e: e[1])
    starts = [e[1] for e in hs]
    longest = max((e[2] for e in hs), default=0.0)
    for a, b in gaps[:top]:
        best, best_ov = "unattributed", 0.0
        for n, s, d in hs[bisect.bisect_left(starts, a - longest):]:
            if s >= b:
                break
            if s + d <= a or d > 4 * (b - a):
                continue
            ov = min(b, s + d) - max(a, s)
            if ov > best_ov:
                best, best_ov = n, ov
        out.append((best, (b - a) / 1e9))
    return out


def top_ops(devices: Dict[str, dict], top: int = 10) -> List[Tuple[str, float]]:
    """The ops that took most device time, seconds averaged over devices.
    Control-flow ops (``while``, ``conditional``, ``call``) hold the ops of
    their bodies, which the line lists too, so they are left out."""
    acc: Dict[str, float] = {}
    for dev in devices.values():
        for n, ns in time_by_name(dev["ops"]).items():
            if CONTAINER.match(n):
                continue
            acc[n] = acc.get(n, 0.0) + ns
    k = max(len(devices), 1)
    return [(n, ns / k / 1e9) for n, ns in
            sorted(acc.items(), key=lambda x: -x[1])[:top]]


def device_index(plane: str) -> int:
    """The id at the end of a device plane's name ("/device:TPU:3" -> 3)."""
    tail = re.search(r"(\d+)\D*$", plane)
    return int(tail.group(1)) if tail else 0


def used(tr: dict, chips: int) -> dict:
    """The trace restricted to the first ``chips`` devices, the ones a
    cell of that many chips runs on."""
    keep = sorted(tr["devices"], key=device_index)[:chips]
    return {**tr, "devices": {k: tr["devices"][k] for k in keep}}


def summarize(tr: dict, window_s: float) -> dict:
    """Device-level numbers of a whole trace: busy seconds averaged over
    the devices, the window, and the breakdown the result line carries."""
    devs = tr["devices"]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    busy = sum(busy_ns(d) for d in devs.values()) / len(devs) / 1e9
    first = sorted(devs)[0]
    return {"busy_s": busy, "window_s": window_s,
            "breakdown": {"device_ops": top_ops(devs),
                          "idle_gaps": idle_gaps(devs[first],
                                                 main_thread(tr["host"]))}}
