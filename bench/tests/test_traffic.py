"""The traffic repeats under a seed, every seed does the same work, and an
escalated request's first token is its wave's return."""
import numpy as np

from bench import harness as H
from bench import latency
from bench import traffic as T
from bench.policy import MarkedEscalation

VOCAB = 49152


def _sig(reqs):
    return [(len(r["prompt"]), r["max_new"], r["at_ms"], r["escalate"],
             r["prompt"][:4].tolist()) for r in reqs]


def test_same_seed_same_traffic():
    mix = T.load_mix("chat-edge")
    mix["rate_rps"] = 2.0
    big = 2**33 + 17                    # seeds wider than 32 bits
    a = T.make_requests(mix, 30, big, VOCAB)
    b = T.make_requests(mix, 30, big, VOCAB)
    assert _sig(a) == _sig(b)
    c = T.make_requests(mix, 30, big + 1, VOCAB)
    assert _sig(a) != _sig(c)


def test_every_seed_the_same_sizes_and_gaps():
    """The same sizes after the same gaps, in the same order, for every
    seed; only the prompts' tokens differ."""
    mix = T.load_mix("chat-edge")
    mix["rate_rps"] = 2.0
    runs = [T.make_requests(mix, 30, s, VOCAB) for s in (1, 2, 3)]
    sizes = [[(len(r["prompt"]), r["max_new"]) for r in reqs]
             for reqs in runs]
    gaps = [np.diff([0.0] + [r["at_ms"] for r in reqs]) for reqs in runs]
    assert sizes[0] == sizes[1] == sizes[2]
    assert sorted(sizes[0]) == sorted(T.window_sizes(mix, 60))
    assert len({r["prompt"][:8].tobytes() for reqs in runs
                for r in reqs[:1]}) == 3
    few = T.window_sizes(mix, 5)
    assert len(few) == 5 and (2048, 256) in few
    assert len(runs[0]) == T.requests_in_window(mix, 30) == 60
    assert max(s[0] for s in sizes[0]) == 2048
    for g in gaps[1:]:
        np.testing.assert_allclose(g, gaps[0], rtol=1e-9)
    # arrivals span the window at the mix's rate
    assert 0 < runs[0][0]["at_ms"]
    assert abs(runs[0][-1]["at_ms"] - 30e3) < 1e-6
    assert [r["at_ms"] for r in runs[0]] == sorted(r["at_ms"]
                                                   for r in runs[0])


def test_sizes_follow_the_mix():
    mix = T.load_mix("chat-edge")
    pairs = T.size_pairs(mix)
    assert len(pairs) == mix["sizes"]
    p = [a for a, _ in pairs]
    m = [b for _, b in pairs]
    assert min(p) >= 64 and max(p) == 2048 and min(m) >= 16 \
        and max(m) == 256
    assert abs(np.median(p) - 384) < 30 and abs(np.median(m) - 96) < 8


def test_warmup_holds_every_prompt_size_and_the_largest_slot():
    mix = T.load_mix("chat-edge")
    pairs = T.size_pairs(mix)
    warm = T.warmup_requests(mix, VOCAB, 16)
    assert T.tick_buckets(16) == [1, 2, 4, 8, 16]
    assert T.tick_buckets(12) == [1, 2, 4, 8, 12]
    assert sorted(len(r["prompt"]) for r in warm) == sorted(a for a, _ in pairs)
    slot = max(len(r["prompt"]) + r["max_new"] for r in warm)
    assert slot == max(a + b for a, b in pairs) == 2048 + 256
    short = [r["max_new"] for r in warm
             if len(r["prompt"]) + r["max_new"] != slot]
    assert len(short) == len(pairs) - 1
    assert set(short) == {1, 2, 4, 8, 16}
    assert all(r["at_ms"] is None and not r["escalate"] for r in warm)


def test_escalation_share_is_exact():
    mix = dict(T.load_mix("chat-edge"), rate_rps=4.0, escalate_share=0.25)
    reqs = T.make_requests(mix, 16, 9, VOCAB)
    assert sum(r["escalate"] for r in reqs) == round(0.25 * len(reqs))


class _Trace:
    def __init__(self, tokens, rounds=0, path="edge"):
        self.tokens, self.cloud_passes, self.path = tokens, rounds, path


class _Engine:
    def __init__(self, events):
        self.events = events


def test_escalated_first_token_is_the_wave_return():
    reqs = [{"prompt": np.arange(10, dtype=np.int32), "max_new": 3,
             "at_ms": 100.0, "escalate": False},
            {"prompt": np.arange(12, dtype=np.int32), "max_new": 3,
             "at_ms": 200.0, "escalate": True}]
    base = 1000.0
    events = {
        0: {"submit_ms": 1100.0, "admit_ms": 1150.0,
            "first_token_ms": 1400.0, "retire_ms": 1600.0},
        # the program stamps an escalated first token at the wave's START
        1: {"submit_ms": 1200.0, "admit_ms": 1250.0,
            "first_token_ms": 1500.0, "retire_ms": 2500.0}}
    results = {0: _Trace([1, 2, 3]), 1: _Trace([4, 5, 6], 3, "speculative")}
    recs = H._records(_Engine(events), results, reqs, [0, 1], base)
    assert recs[0]["first_ms"] == 400.0 and recs[0]["due_ms"] == 100.0
    assert recs[1]["first_ms"] == recs[1]["retire_ms"] == 1500.0
    assert latency.ttfts(recs) == [300.0, 1300.0]
    assert latency.tpots(recs) == [100.0, 0.0]


def test_percentile_is_inclusive_interpolation():
    xs = list(range(1, 11))
    assert latency.percentile(xs, 90) == 9.1
    assert latency.percentile([5.0], 90) == 5.0
    assert latency.percentile([], 90) is None


def test_marking_policy():
    pol = MarkedEscalation("speculative")
    pol.mark(3)
    assert pol.assign({"rid": 3}) == "collab"
    assert pol.assign({"rid": 4}) == "edge"
    assert pol.decide(np.zeros(2), np.zeros(2), np.zeros(2)) == \
        ["speculative"] * 2
    pol.feedback("speculative", 1.0, 5, {"rid": 3, "draft": [7, 8]})
    assert pol.drafts == {3: [7, 8]}
