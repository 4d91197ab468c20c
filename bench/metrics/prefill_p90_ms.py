"""Chunked prefill: 90th percentile over finished requests of the time from
admission to the end of prefill (``prefill_done_ms - admit_ms``, the
engine's own stamps: a chunked prefill's finalize, or the admission itself
for a whole-prompt one).  Reads ``prefill_done_ms`` from the request
records; records without it give nothing."""
from bench import latency


def read(run):
    return latency.percentile([r["prefill_done_ms"] - r["admit_ms"]
                               for r in latency.completed(run.records)
                               if r.get("prefill_done_ms") is not None], 90)
