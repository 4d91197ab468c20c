"""90th percentile over finished requests of the time from each one's due
time to its first token (host clock); an escalated request's first token
is its wave's return."""
from bench import latency


def read(run):
    return latency.percentile(latency.ttfts(run.records), 90)
