"""The lower-precision control fails the comparison that sound runs pass:
the float8 forward, read at the served prompts and tokens, lies further
below the float32 reference's best than the program's own tokens do.
The control's tokens take the served tokens' place in the harness's own
verdict, which comes out false.  At the cell's own size this is read on
the chip (``--control 1``); here the whole harness runs at a tiny size on
the CPU."""
import pytest

from bench.tests import _tiny


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


@pytest.mark.parametrize("kind,seed", [("chat", 2**33 + 1), ("esc", 17)])
def test_control_fails_where_the_program_passes(kind, seed):
    res = _tiny.run(kind, seed, control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    ctrl = res["control"]
    assert ctrl["correct"] is False, ctrl
    for name, low in ctrl["checks"].items():
        got = res["checks"][name]
        assert low["limit"] == got["limit"]
        assert got["value"] <= got["limit"] < low["value"], (name, got, low)
        assert low["value"] >= 3 * got["value"], (name, got, low)
