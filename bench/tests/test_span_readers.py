"""The readers of the program's spans and stamps (``prefill_p90_ms``,
``host_gap_ms``, ``decode_slot_util``) on small runs placed by hand: a
trace in ``trace.normalize``'s format, a span list and request records."""
import gzip
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness as H
from repro.core.spans import Span

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6        # ns per ms


def _run(**kw):
    base = {"trace": None, "records": []}
    return SimpleNamespace(**{**base, **kw})


def _trace(ops, host):
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "host": host}


# ---------------------------------------------------------------- host gap
# two decoding ticks of 100 ms, each with its dispatch and readback; the
# device runs ops around and across them.  Idle inside tick 1 outside its
# readback [60, 90]: [0, 10] + [30, 50] + [90, 95] = 35 ms; inside tick 2
# outside [250, 290]: [200, 240] + [290, 300] = 50 ms.  The device gap
# [120, 200] lies between ticks, and [70, 90] inside a readback: neither
# counts.  Tick 3, [400, 450], dispatches nothing (every slot mid-prefill):
# its 50 idle ms are not a decoding tick's, so neither it nor its gap
# counts.
OPS = [["fusion.1", 10 * MS, 20 * MS], ["fusion.2", 50 * MS, 20 * MS],
       ["copy.3", 95 * MS, 25 * MS], ["fusion.1", 240 * MS, 20 * MS]]
TICKS = [["engine.tick", 0.0, 100 * MS], ["engine.tick", 200 * MS, 100 * MS],
         ["engine.tick", 400 * MS, 50 * MS]]
READBACKS = [["edge.readback", 60 * MS, 30 * MS],
             ["edge.readback", 250 * MS, 40 * MS]]
DISPATCHES = [["edge.dispatch", 55 * MS, 5 * MS],
              ["edge.dispatch", 245 * MS, 5 * MS]]


@pytest.mark.parametrize("lines", [
    {"python3": TICKS + DISPATCHES + READBACKS},          # one line
    {"python3": [["$scheduler.py:420 _run_impl", 0.0, 450 * MS]] + TICKS,
     "main/291": DISPATCHES + READBACKS},                 # split over lines
])
def test_host_gap_by_hand(lines):
    got = H.reader("host_gap_ms")(_run(trace=_trace(OPS, lines)))
    assert got == pytest.approx((35 + 50) / 2)


def test_host_gap_busy_device_and_no_spans():
    busy = [["fusion.9", -1 * MS, 400 * MS]]
    read = H.reader("host_gap_ms")
    assert read(_run(trace=_trace(
        busy, {"python3": TICKS + DISPATCHES + READBACKS}))) == 0.0
    # ticks that dispatch nothing: no decoding tick to read
    assert read(_run(trace=_trace(OPS, {"python3": TICKS + READBACKS}))) \
        is None
    assert read(_run(trace=_trace(OPS, {"python3": [["$frame", 0.0, MS]]}))) \
        is None
    assert read(_run()) is None


def test_host_gap_minus():
    spec = importlib.util.spec_from_file_location(
        "host_gap_ms", H.METRIC_DIR / "host_gap_ms.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.minus([(0, 10), (11, 15)], [(2, 3), (5, 12)]) == \
        [(0, 2), (3, 5), (12, 15)]
    assert mod.minus([(0, 10)], []) == [(0, 10)]
    assert mod.minus([(0, 10)], [(-5, 20)]) == []


def test_host_gap_recorded_trace_has_no_program_spans():
    """The recorded v5e slice predates the program's spans: nothing."""
    with gzip.open(DATA / "v5e_chat_trace.json.gz", "rt") as f:
        recorded = json.load(f)
    assert H.reader("host_gap_ms")(_run(trace=recorded)) is None


# ---------------------------------------------------------------- slot use
def _tick(active, run, **kw):
    return Span("engine.tick", 0.0, 1.0,
                counts={"slot_steps_active": active, "slot_steps_run": run,
                        **kw})


def test_decode_slot_util_by_hand():
    spans = [_tick(10, 64), Span("edge.dispatch", 0.2, 0.5, parent=0,
                                 counts={"n_steps": 4}),
             _tick(6, 16), Span("engine.tick", 2.0, 3.0)]  # no dispatch
    got = H.reader("decode_slot_util")(_run(spans=spans))
    assert got == pytest.approx(100.0 * 16 / 80)


def test_decode_slot_util_without_spans():
    read = H.reader("decode_slot_util")
    assert read(_run()) is None
    assert read(_run(spans=[])) is None
    assert read(_run(spans=[Span("engine.tick", 0.0, 1.0)])) is None


# ---------------------------------------------------------------- prefill
def _rec(admit, done, tokens=(1, 2)):
    return {"admit_ms": admit, "prefill_done_ms": done, "retire_ms": 9e9,
            "tokens": list(tokens)}


def test_prefill_p90_by_hand():
    recs = [_rec(0.0, 100.0 * i) for i in range(11)]     # 0 .. 1000 ms
    recs.append({**_rec(0.0, 5e5), "tokens": None})      # unfinished
    assert H.reader("prefill_p90_ms")(_run(records=recs)) == \
        pytest.approx(900.0)


def test_prefill_p90_without_stamps():
    recs = [{"admit_ms": 0.0, "retire_ms": 1.0, "tokens": [1]}] * 3
    assert H.reader("prefill_p90_ms")(_run(records=recs)) is None
