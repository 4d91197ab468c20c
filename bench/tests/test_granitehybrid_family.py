"""The granitehybrid family (granite-4.0-h-micro's block) at a test's
size, with both layer kinds: its weights and reference pinned, the
harness serving it through the program's paged engine and finding the
reference's tokens, and the ``state_step_roofline`` reader on a run placed
by hand."""
import copy
import hashlib
from types import SimpleNamespace
from unittest import mock

import jax
import numpy as np
import pytest

from bench import families
from bench import flops as F
from bench import harness as H
from bench import work
from bench.families import granitehybrid as GH
from bench.tests import _tiny
from repro.core.spans import Span

SEED = 2**33 + 5
MS = 1e6        # ns per ms
# both layer kinds of the granite-4.0-h block at a test's size
HYBRID = {"name": "tiny-hybrid", "family": "granitehybrid", "num_layers": 4,
          "layer_types": ["mamba", "attention", "mamba", "mamba"],
          "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
          "d_ff": 128, "vocab_size": 512, "mlp_activation": "silu",
          "use_rope": False, "norm_eps": 1e-5, "tie_embeddings": True,
          "ssm_state": 16, "ssm_head_dim": 16, "ssm_expand": 2,
          "ssm_chunk": 8, "conv_kernel": 4, "embedding_multiplier": 12.0,
          "attention_multiplier": 1 / 16, "residual_multiplier": 0.22,
          "logits_scaling": 8.0, "param_dtype": "bfloat16",
          "activ_dtype": "bfloat16"}
HYBRID_CFG = {"name": "tiny-hybrid", "edge": HYBRID, "cloud": _tiny.CLOUD,
              "limits": {"edge_gap": 0.02}}
# sha256[:16] of each leaf's bytes of HYBRID under SEED, and of the float32
# (then float8-control) reference logits at the prompts of ``_prompts``
PINS = {
    "weights": {
        "['attention']['attn']['wk']": "086ca1f9f376ec61",
        "['attention']['attn']['wo']": "b2dad285ac5ed0ea",
        "['attention']['attn']['wq']": "c2aacb1c0c30aa8f",
        "['attention']['attn']['wv']": "2eb4f366cc0559b4",
        "['attention']['mlp']['w_down']": "75753d77bc758250",
        "['attention']['mlp']['w_gate']": "b79ee73f2afa25dc",
        "['attention']['mlp']['w_up']": "6f031f363333acad",
        "['attention']['mlp_norm']": "2af0fcf8e8848600",
        "['attention']['norm']": "9c4bcf3f2fd88aa6",
        "['embed']": "df2ea27bc3d730bb",
        "['final_norm']": "c57fc06af84c6b42",
        "['mamba']['mixer']['A_log']": "b723cab211778f6a",
        "['mamba']['mixer']['D']": "758992ec1bffaabe",
        "['mamba']['mixer']['conv']['b']": "baabdf4ddfb487c1",
        "['mamba']['mixer']['conv']['w']": "1bdfc9a0cbdaf9b2",
        "['mamba']['mixer']['dt_bias']": "fa9dc11bead0159e",
        "['mamba']['mixer']['in_dt']": "5565f763edbb98f4",
        "['mamba']['mixer']['in_xbc']": "fddead2c74b05565",
        "['mamba']['mixer']['in_z']": "6448be7494fb70c5",
        "['mamba']['mixer']['norm']": "22859ccbf42dc56f",
        "['mamba']['mixer']['out_proj']": "c439954879f59f3e",
        "['mamba']['mlp']['w_down']": "80fb1e767c09bac5",
        "['mamba']['mlp']['w_gate']": "e49548104c24efa1",
        "['mamba']['mlp']['w_up']": "83ebeb5de73dd39c",
        "['mamba']['mlp_norm']": "4aab4ce4558db4d6",
        "['mamba']['norm']": "41d6bfc2cb5968f7"},
    "logits": ["8652afd4f5a698f5", "3f4b4c60ec791c42"]}


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def _sha(x) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes()
                          ).hexdigest()[:16]


def _prompts(vocab):
    rng = np.random.default_rng(3)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in (37, 20)]


def _run(**kw):
    base = {"trace": None, "records": []}
    return SimpleNamespace(**{**base, **kw})


def test_weights_and_reference_pinned():
    fam = families.of(HYBRID)
    assert fam is GH
    params = fam.make_on_device(SEED, 0, HYBRID)
    got = {jax.tree_util.keystr(k): _sha(v)
           for k, v in jax.tree_util.tree_leaves_with_path(params)}
    assert got == PINS["weights"]
    seqs = _prompts(HYBRID["vocab_size"])
    lg = [_sha(np.concatenate(fam.logits_at(HYBRID, SEED, 0, seqs, [30, 5],
                                            fp8=fp8)))
          for fp8 in (False, True)]
    assert lg == PINS["logits"]


@pytest.mark.parametrize("seed", [41, 2**33 + 7])
def test_cell_runs_correct(seed):
    """The harness serves the hybrid edge through the program's paged
    engine and finds its tokens the reference's."""
    spec = H.load_spec()
    cell = {"name": "hmicro-chat-short", "chips": 1}
    with mock.patch.object(H, "require_chips", _tiny._cpu):
        res = H.run_cell(cell["name"], cell, copy.deepcopy(HYBRID_CFG),
                         copy.deepcopy(_tiny.CHAT), spec, seed=seed,
                         seconds=3.0, trace=False)
    assert res["correct"] and res["attempted"] > 0, res["checks"]
    assert res["checks"]["edge_gap"]["value"] < 1e-3


def test_state_step_roofline_by_hand(capsys):
    """Two steps of the tiny hybrid in a traced slice of 100 ms whose tick
    program ran 10 ms: the edge's weights twice, one request emitting
    through the whole slice (2 of its 4 tokens inside), its state read
    and written, and its K/V at its live lengths, over 10 ms at the
    chip's peak bandwidth."""
    L = GH.paged_layers(HYBRID)
    trace = {"devices": {"/device:TPU:0": {
        "ops": [["paged_decode_attention.1", 0.0, MS]] * (2 * L),
        "modules": [["jit_chunk", 0.0, 10 * MS]]}}, "host": {}}
    recs = [{"prompt": [1] * 9, "tokens": [1, 2, 3, 4], "escalated": False,
             "admit_ms": -100.0, "first_ms": -50.0, "retire_ms": 150.0}]
    spans = [Span("edge.dispatch", 0.0, 1.0,
                  counts={"n_steps": 4, "state_rows": 2})]
    run = _run(trace=trace, records=recs, cfg={"edge": HYBRID},
               trace_window=(0.0, 100.0), device_kind="TPU v5 lite",
               spans=spans)
    kv = work.edge_decode_attention(run, 0.0, 100.0)["bytes"]
    need = (2 * GH.weight_bytes(HYBRID) + 2 * 2.0 * GH.state_bytes(HYBRID)
            + kv) / F.peaks("TPU v5 lite")["hbm_bytes_s"]
    got = H.reader("state_step_roofline")(run)
    assert got == pytest.approx(100.0 * need / 10e-3)
    assert "rows moved=8" in capsys.readouterr().err


def test_state_step_roofline_needs_state_and_a_trace():
    read = H.reader("state_step_roofline")
    assert read(_run(cfg={"edge": HYBRID})) is None
    # a family without recurrent state gives nothing, trace or not
    trace = {"devices": {"/device:TPU:0": {
        "ops": [["paged_decode_attention.1", 0.0, MS]],
        "modules": [["jit_chunk", 0.0, MS]]}}, "host": {}}
    assert read(_run(trace=trace, cfg={"edge": _tiny.EDGE})) is None
