"""Host loop: device idle time per decoding tick that the host's own
steps leave.  In the traced slice, the time in which no op runs on the
device that falls inside the program's decoding ``engine.tick`` spans (a
tick that holds an ``edge.dispatch`` step: it runs the decode program)
but outside their ``edge.readback`` step (where the host waits on the
device), over the number of decoding ticks in the slice, in ms.  Ticks
in which every occupied slot is mid-prefill set the time to first token
and are left out.  Reads the profiler's copy of the spans
(``core/spans.py`` annotates each one), found by name on every host
line: the runtime, not the benchmark, picks the line.  A trace without
a decoding tick gives nothing."""
import bisect

from bench import trace as TR

TICK = "engine.tick"
READBACK = "edge.readback"
DISPATCH = "edge.dispatch"


def host_spans(host, name):
    """Intervals of the events called ``name`` on any host line."""
    return [(s, s + d) for line in host.values() for n, s, d in line
            if n == name]


def minus(a, b):
    """The parts of the sorted, disjoint intervals ``a`` that no interval
    of the sorted, disjoint ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    host_lines = run.trace["host"]
    starts = sorted(s for s, _ in host_spans(host_lines, DISPATCH))
    ticks = [(s, e) for s, e in host_spans(host_lines, TICK)
             if bisect.bisect_left(starts, e) > bisect.bisect_left(starts, s)]
    if not ticks:
        return None
    devs = run.trace["devices"]
    busy = TR.union(TR.spans(devs[sorted(devs)[0]]["ops"]))
    host = minus(TR.union(ticks),
                 TR.union(host_spans(host_lines, READBACK)))
    return TR.total(minus(host, busy)) / len(ticks) / 1e6
