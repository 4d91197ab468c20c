"""Model step: the share of the slot-steps the edge tick runs that emit a
token.  Every tick decodes all ``batch`` slots for ``n_steps`` steps; over
every ``engine.tick`` span of the measured window's drain, 100 x the
summed ``slot_steps_active`` (slot-steps that emitted a token, counted
from the tick's readback) over the summed ``slot_steps_run`` (``n_steps x
batch``), in %.  Reads the engine's ``SpanLog`` spans as ``run.spans``; a
run without them gives nothing."""

TICK = "engine.tick"


def read(run):
    ticks = [s.counts for s in getattr(run, "spans", None) or ()
             if s.name == TICK]
    ran = sum(c.get("slot_steps_run", 0) for c in ticks)
    if not ran:
        return None
    return 100.0 * sum(c.get("slot_steps_active", 0) for c in ticks) / ran
