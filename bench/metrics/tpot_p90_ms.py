"""90th percentile over finished requests of (last token - first token) /
(tokens - 1) (host clock)."""
from bench import latency


def read(run):
    return latency.percentile(latency.tpots(run.records), 90)
