"""Model families: the dense family pinned to the values it gave before it
had a file of its own, a family that exists only in a temporary directory
taken up by the harness, the reference, the work counts and
``edge_step_ms`` with no other edit, an unknown family refused, and a
traced run handing its readers the engine's spans."""
import hashlib
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import families
from bench import harness as H
from bench import trace as TR
from bench import work
from bench.families import dense as D
from bench.tests import _tiny

SEED = 2**33 + 5
# sha256[:16] of each leaf's bytes of ``_tiny``'s models under SEED, and of
# the float32 (then float8-control) reference logits at the prompts of
# ``_prompts``, as the benchmark gave them before the families existed
PINS = {
    "edge": {
        "weights": {
            "['blocks']['attn']['wk']": "d6b704438350174c",
            "['blocks']['attn']['wo']": "9ee37f9a2137ddc6",
            "['blocks']['attn']['wq']": "78f2a1c1a0660960",
            "['blocks']['attn']['wv']": "2b1bd889129dcc6c",
            "['blocks']['attn_norm']": "8c1d5aa168236f06",
            "['blocks']['mlp']['w_down']": "7b26c0c6e8bf2663",
            "['blocks']['mlp']['w_gate']": "6e7410503f86fca5",
            "['blocks']['mlp']['w_up']": "4501f7d33b13d882",
            "['blocks']['mlp_norm']": "c426f9b101674e9e",
            "['embed']": "88db0dda799edb31",
            "['final_norm']": "c57fc06af84c6b42"},
        "logits": ["66df103887f3cdb8", "8d057852ab6fa13a"]},
    "cloud": {
        "weights": {
            "['blocks']['attn']['wk']": "e7c565c0fdd090e6",
            "['blocks']['attn']['wo']": "82f8d9237acc2dec",
            "['blocks']['attn']['wq']": "7d3e93812dae2ee3",
            "['blocks']['attn']['wv']": "33ddf6547b697973",
            "['blocks']['attn_norm']": "5a9c998384fa8403",
            "['blocks']['mlp']['w_down']": "571e30587e3caaed",
            "['blocks']['mlp']['w_gate']": "5e9feeb7c8efad24",
            "['blocks']['mlp']['w_up']": "8d411fa58c1adfcd",
            "['blocks']['mlp_norm']": "3a6b43843b188e33",
            "['embed']": "5ce6f15ef4a1fb02",
            "['final_norm']": "e3760874928fa229"},
        "logits": ["08ce1b2078f447e2", "bdcff9fc1b90cd31"]},
}

# a family file that only this test has, in a directory of its own under
# the name the program knows (``dense``, so the program serves it): the
# benchmark's dense family with its weights drawn under other model keys
# (index + 100), one layer of paged decode attention, and every call of
# the contract logged
MARKED = '''
from bench.families import dense as D

CALLS = []


def make_on_device(seed, index, cfg):
    CALLS.append("make_on_device")
    return D.make_on_device(seed, index + 100, cfg)


def logits_at(cfg, seed, index, seqs, starts, fp8=False, device=None):
    CALLS.append("logits_at")
    return D.logits_at(cfg, seed, index + 100, seqs, starts, fp8, device)


def token_flops(cfg):
    CALLS.append("token_flops")
    return D.token_flops(cfg)


def attn_flops(cfg, ctx_sum):
    CALLS.append("attn_flops")
    return D.attn_flops(cfg, ctx_sum)


def paged_layers(cfg):
    CALLS.append("paged_layers")
    return 1
'''


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def _sha(x) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes()
                          ).hexdigest()[:16]


def _prompts(vocab):
    rng = np.random.default_rng(3)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in (37, 20)]


# ---------------------------------------------------------------- dense pin
@pytest.mark.parametrize("index,model", [(0, "edge"), (1, "cloud")])
def test_dense_weights_and_reference_pinned(index, model):
    cfg = _tiny.CFG[model]
    fam = families.of(cfg)
    assert fam is D
    params = fam.make_on_device(SEED, index, cfg)
    got = {jax.tree_util.keystr(k): _sha(v)
           for k, v in jax.tree_util.tree_leaves_with_path(params)}
    assert got == PINS[model]["weights"]
    seqs = _prompts(cfg["vocab_size"])
    lg = [_sha(np.concatenate(fam.logits_at(cfg, SEED, index, seqs,
                                            [30, 5], fp8=fp8)))
          for fp8 in (False, True)]
    assert lg == PINS[model]["logits"]


# ---------------------------------------------------------------- lookup
@pytest.mark.parametrize("cfg", [{"family": "nope"}, {"family": "_tiny"},
                                 {"family": "__init__"}, {}])
def test_unknown_family_names_the_known(cfg):
    with pytest.raises(KeyError, match=r"known: \['dense'\]"):
        families.of(cfg)


def test_same_module_every_time():
    assert families.of({"family": "dense"}) is families.of(_tiny.EDGE) is D


# ---------------------------------------------------------------- new family
def _marked(tmp_path, monkeypatch):
    fam_dir = tmp_path / "families"
    fam_dir.mkdir()
    (fam_dir / "dense.py").write_text(MARKED)
    monkeypatch.setattr(families, "FAMILY_DIR", fam_dir)
    fam = families.of(_tiny.EDGE)
    assert fam is not D and fam.D is D
    return fam


def test_build_hands_the_family_name_to_the_program(tmp_path, monkeypatch):
    """A family with a file and a name of its own gets through the
    harness: ``_build`` draws its weights from its file and hands the same
    name to the program, which here refuses it (the program, not the
    benchmark, must serve a new family).  A family with no file fails
    before the program builds anything."""
    import repro.models
    fam_dir = tmp_path / "families"
    fam_dir.mkdir()
    (fam_dir / "marked.py").write_text(MARKED)
    monkeypatch.setattr(families, "FAMILY_DIR", fam_dir)
    cfg = {m: dict(_tiny.CFG[m], family="marked") for m in ("edge", "cloud")}
    mix = _tiny.CHAT
    fam = families.of(cfg["edge"])
    with pytest.raises(ValueError, match="'marked' / 'marked'"):
        H._build(cfg, mix, 41)
    assert fam.CALLS == ["make_on_device", "make_on_device"]
    built = []
    monkeypatch.setattr(repro.models, "Model",
                        lambda c: built.append(c.family))
    for m in ("edge", "cloud"):
        cfg[m]["family"] = "nope"
        with pytest.raises(KeyError, match=r"known: \['marked'\]"):
            H._build(cfg, mix, 41)
        cfg[m]["family"] = "marked"
    assert built == []


def _kernel_trace(calls, tick_ms):
    ops = [["paged_decode_attention.1", 1e6 * i, 1e5] for i in range(calls)]
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_chunk", 0.0, tick_ms * 1e6]]}},
        "host": {}}


def test_a_family_file_alone_is_taken_up(tmp_path, monkeypatch):
    dense_dir = families.FAMILY_DIR
    fam = _marked(tmp_path, monkeypatch)
    cfg = _tiny.CFG
    seed = 41
    s = _tiny.session("chat", seed)
    assert fam.CALLS.count("make_on_device") == 2
    # the served weights are the marked ones, not dense's at index 0
    want = D.make_on_device(seed, 100, _tiny.EDGE)
    for a, b in zip(jax.tree.leaves(s.params[0]), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    res = s.measure(seed, 3.0, False)
    # the reference drew the marked weights too, or the gap would be wide
    assert "logits_at" in fam.CALLS
    assert res["correct"] and res["attempted"] > 0, res["checks"]
    # work arithmetic only: a chip kind for the peaks table, no device time
    run = SimpleNamespace(**{**s.run.__dict__, "device_kind": "TPU v5 lite"})
    win = (0.0, run.seconds * 1e3)
    del fam.CALLS[:]
    marked = {"attn": work.edge_decode_attention(run, *win),
              "mfu": work.mfu(run),
              "step": H.reader("edge_step_ms")(
                  SimpleNamespace(trace=_kernel_trace(6, 12.0), cfg=cfg))}
    assert {"token_flops", "attn_flops", "paged_layers"} <= set(fam.CALLS)
    # the same run under the real dense family: only the layers that run
    # the paged kernel differ
    monkeypatch.setattr(families, "FAMILY_DIR", dense_dir)
    assert families.of(cfg["edge"]) is not fam
    L = cfg["edge"]["num_layers"]
    attn = work.edge_decode_attention(run, *win)
    assert marked["attn"]["flops"] > 0
    assert marked["attn"] == {k: v / L for k, v in attn.items()}
    assert marked["mfu"] == work.mfu(run) > 0
    assert marked["step"] == 12.0 / 6
    assert H.reader("edge_step_ms")(SimpleNamespace(
        trace=_kernel_trace(6, 12.0), cfg=cfg)) == 12.0 / (6 / L)


# ---------------------------------------------------------------- spans
def test_traced_run_hands_readers_the_spans(tmp_path, monkeypatch):
    """A traced window gives the engine a ``SpanLog`` and its ``Run`` the
    spans; the records carry the engine's prefill and first-token stamps.
    The profiler is left out (the CPU has no device plane to reduce): the
    reduction gets a one-op trace in its place."""
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(TR, "load", lambda d: _kernel_trace(1, 1.0))
    monkeypatch.setattr(H, "TRACE_DIR", tmp_path / "trace")
    spec = H.load_spec()
    # the readers a CPU run can feed: none of a device's peaks
    spec["per_layer"] = [m for m in spec["per_layer"] if m["name"] in
                         ("prefill_p90_ms", "decode_slot_util",
                          "host_gap_ms", "queue_wait_p90_ms")]
    seed = 43
    s = _tiny.session("chat", seed, spec=spec)
    assert s.engine.spans is None
    res = s.measure(seed, 3.0, True)
    assert res["correct"], res["checks"]
    assert s.engine.spans is None
    names = {sp.name for sp in s.run.spans}
    assert {"engine.tick", "edge.readback", "edge.dispatch"} <= names
    done = [r for r in s.run.records if r["tokens"] is not None]
    assert done and all(r["prefill_done_ms"] is not None
                        and r["first_token_ready_ms"] is not None
                        and r["admit_ms"] <= r["prefill_done_ms"]
                        for r in done)
    assert set(res["metrics"]) == {"prefill_p90_ms", "decode_slot_util",
                                   "queue_wait_p90_ms"}
    assert 0 < res["metrics"]["decode_slot_util"]["value"] <= 100
    # an untraced window records none
    s.reseed(seed)
    s.measure(seed, 3.0, False)
    assert s.run.spans is None and s.engine.spans is None
