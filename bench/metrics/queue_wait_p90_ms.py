"""Scheduler: 90th percentile over finished requests of the wait from
each one's due time to its admission into a slot (the engine's own
admission stamp)."""
from bench import latency


def read(run):
    return latency.percentile([r["admit_ms"] - r["due_ms"]
                               for r in latency.completed(run.records)], 90)
