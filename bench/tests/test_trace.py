"""The trace reduction: interval arithmetic by hand, and the reduction of
a small trace recorded on a v5e chip against a naive recount."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"


def test_union():
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert TR.total(TR.union([(0, 2), (1, 3)])) == 3


def test_busy_and_gaps():
    dev = {"ops": [["all-reduce.3", 0.0, 10.0], ["fusion.1", 5.0, 10.0],
                   ["custom-call", 40.0, 5.0], ["all-gather", 100.0, 4.0]],
           "modules": []}
    assert TR.busy_ns(dev) == 15 + 5 + 4
    host = [["run", 0.0, 1000.0],            # encloses everything
            ["device_get", 16.0, 20.0],      # inside the first gap
            ["sleep", 50.0, 45.0]]           # inside the second gap
    gaps = TR.idle_gaps(dev, host)
    assert gaps == [("sleep", 55e-9), ("device_get", 25e-9)]
    assert TR.main_thread({"a": host[:1], "b": host}) == host
    assert TR.short("%fusion.3 = bf16[8]{0} fusion(...)") == "fusion.3"
    assert TR.matching(dev["ops"], r"^all-")[1] == 2


def _naive_busy_ns(ops, step=1000.0):
    """Busy time on a 1 us grid: slow, obviously right."""
    if not ops:
        return 0.0
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    grid = np.zeros(int((t1 - t0) // step) + 2, bool)
    for _, s, d in ops:
        grid[int((s - t0) // step):int(-(-(s + d - t0) // step))] = True
    return grid.sum() * step


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA / "v5e_chat_trace.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_trace_reduction(recorded):
    devs = recorded["devices"]
    assert len(devs) == 1
    dev = next(iter(devs.values()))
    busy = TR.busy_ns(dev)
    assert busy == pytest.approx(_naive_busy_ns(dev["ops"]), rel=0.01)
    summ = TR.summarize(recorded, recorded["window_s"])
    assert summ["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < summ["busy_s"] <= summ["window_s"]
    ops = summ["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops == sorted(ops, key=lambda x: -x[1])
    assert sum(s for _, s in ops) <= summ["busy_s"] + 1e-9
    gaps = summ["breakdown"]["idle_gaps"]
    assert gaps == sorted(gaps, key=lambda x: -x[1]) and len(gaps) == 10
    for name, want in recorded["expect"]["matching"].items():
        ns, k = TR.matching(dev["ops"] if want["line"] == "ops"
                            else dev["modules"], name)
        assert k == want["count"] and ns == pytest.approx(want["ns"])
