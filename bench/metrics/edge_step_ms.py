"""Model step: device milliseconds of the edge tick program per decode
step it ran, in the traced sub-window.  Steps are counted as the paged
decode kernel's calls over the edge's layers that run it (its family's
``paged_layers``: one call per such layer per step)."""
from bench import families
from bench import trace as TR

TICK = r"jit_chunk"
KERNEL = r"paged_decode"


def read(run):
    if run.trace is None:
        return None
    tick_ns = calls = 0.0
    for d in run.trace["devices"].values():
        tick_ns += TR.matching(d["modules"], TICK)[0]
        calls += TR.matching(d["ops"], KERNEL)[1]
    edge = run.cfg["edge"]
    steps = calls / families.of(edge).paged_layers(edge)
    if not tick_ns or not steps:
        return None
    return tick_ns / steps / 1e6
