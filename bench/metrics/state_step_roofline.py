"""Model step, recurrent state: the share of the edge tick program's
device time that the HBM bytes its decode steps need would take at the
chip's peak bandwidth, in the traced sub-window.  The bytes: the edge's
weights once per step (the family's ``weight_bytes``; steps counted as the
paged decode kernel's calls over the family's ``paged_layers``), every
emitting slot-step's recurrent state read and written (the family's
``state_bytes``; emitting slot-steps from the request records, each
decode phase spread evenly over its interval), and the K/V the attention
layers read at the live lengths (``work.edge_decode_attention``).  Over
the summed device time of the tick program (``jit_chunk``), in %.

It also logs, on stderr, the slot rows of recurrent state the program
read and wrote in the window (the engine's ``edge.dispatch`` counters,
``state_rows`` x ``n_steps``) beside the emitting slot-steps.  A family
without ``state_bytes``, or a run without a trace, gives nothing."""
import sys

from bench import families
from bench import flops as F
from bench import trace as TR
from bench import work

TICK = r"jit_chunk"
KERNEL = r"paged_decode"
DISPATCH = "edge.dispatch"


def rows_moved(spans) -> int:
    """Slot rows of recurrent state the window's tick programs read and
    wrote: ``state_rows`` x ``n_steps`` over the decoding ticks."""
    return sum(s.counts.get("state_rows", 0) * s.counts.get("n_steps", 0)
               for s in spans or () if s.name == DISPATCH)


def read(run):
    cfg = run.cfg["edge"]
    fam = families.of(cfg)
    if run.trace is None or not hasattr(fam, "state_bytes"):
        return None
    tick_ns = calls = 0.0
    for d in run.trace["devices"].values():
        tick_ns += TR.matching(d["modules"], TICK)[0]
        calls += TR.matching(d["ops"], KERNEL)[1]
    steps = calls / fam.paged_layers(cfg)
    if not tick_ns or not steps:
        return None
    t0, t1 = run.trace_window
    active = sum(work.share(r["first_ms"], r["retire_ms"], t0, t1)
                 * len(r["tokens"]) for r in work.edge_served(run))
    kv = work.edge_decode_attention(run, t0, t1)["bytes"]
    nbytes = steps * fam.weight_bytes(cfg) \
        + 2.0 * active * fam.state_bytes(cfg) + kv
    need_s = nbytes / F.peaks(run.device_kind)["hbm_bytes_s"]
    print(f"state_step_roofline: steps={steps:.0f} emitting_slot_steps="
          f"{active:.1f} bytes={nbytes:.6g} tick_s={tick_ns / 1e9:.6g}; "
          f"window state rows moved={rows_moved(getattr(run, 'spans', None))}",
          file=sys.stderr, flush=True)
    return 100.0 * need_s / (tick_ns / 1e9)
