"""Kernels: the paged decode attention kernel's share of its roofline in
the traced sub-window.  The least time is the larger of the FLOPs and the
HBM bytes that attention at the live lengths needs (q, o, each live
sequence's K and V up to its length; not the blocks the grid reads),
over the chip's peaks; the time is the summed device time of the kernel's
events.  In %."""
from bench import flops as F
from bench import trace as TR
from bench import work

KERNEL = r"paged_decode"


def read(run):
    if run.trace is None:
        return None
    ns = sum(TR.matching(d["ops"], KERNEL)[0]
             for d in run.trace["devices"].values())
    if not ns:
        return None
    need = work.edge_decode_attention(run, *run.trace_window)
    t = F.roofline_time(need["flops"], need["bytes"],
                        F.peaks(run.device_kind))["seconds"]
    return 100.0 * t / (ns / 1e9)
