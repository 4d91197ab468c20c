"""The float32 reference against the program's own forward pass at a
small size on the CPU, on the benchmark's seeded weights (the dense
family's)."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench.families import dense as D

CFG = {"name": "tiny", "family": "dense", "num_layers": 3, "d_model": 64,
       "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 96,
       "vocab_size": 300, "mlp_activation": "silu", "rope_theta": 10000.0,
       "norm_eps": 1e-5, "tie_embeddings": True, "param_dtype": "float32",
       "activ_dtype": "float32"}
SEED = 2**32 + 3


def test_stacked_leaves_equal_per_layer_draws():
    key = W.model_key(SEED, 1)
    full = D.program_params(key, CFG)
    for layer in range(CFG["num_layers"]):
        one = D.layer_params(key, layer, CFG)
        np.testing.assert_array_equal(np.asarray(full["blocks"]["attn"]["wk"][layer]),
                                      np.asarray(one["wk"]))
        np.testing.assert_array_equal(np.asarray(full["blocks"]["mlp"]["w_down"][layer]),
                                      np.asarray(one["w_down"]))
        np.testing.assert_array_equal(np.asarray(full["blocks"]["mlp_norm"][layer]),
                                      np.asarray(one["mlp_norm"]))
    assert full["embed"].dtype == jnp.bfloat16


def test_reference_matches_program_prefill_logits():
    from repro.configs.base import ModelConfig
    from repro.models import transformer
    cfg = ModelConfig(**CFG)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          D.program_params(W.model_key(SEED, 0), CFG))
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 300, n).astype(np.int32) for n in (37, 20)]
    starts = [30, 5]
    ref = D.logits_at(CFG, SEED, 0, seqs, starts)
    for s, st, lg in zip(seqs, starts, ref):
        with jax.default_matmul_precision("highest"):
            prog, _ = transformer.forward(params, jnp.asarray(s[None]), cfg)
        want = np.asarray(prog[0, st - 1:len(s) - 1])
        assert lg.shape == want.shape
        np.testing.assert_allclose(lg, want, rtol=2e-4, atol=2e-4)


def test_widest_gap_and_control():
    lg = [np.array([[0.0, 1.0, 0.5], [2.0, 0.0, 1.9]])]
    assert R.widest_gap(lg, [[1, 0]]) == 0.0
    assert np.isclose(R.widest_gap(lg, [[2, 2]]), 0.5)
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, 300, 40).astype(np.int32)]
    out = R.compare(CFG, SEED, 0, [seqs[0][:30]], [seqs[0][30:]],
                    control=True)
    assert out["tokens"] == 10 and out["gap"] > 0
    assert out["control_gap"] >= 0


def test_sample_keeps_the_longest():
    fin = [(i, n) for i, n in enumerate([5, 50, 7, 9, 11, 3])]
    pick = R.pick_sample(fin, 4, 60, 8)
    assert pick[0] == 1 and len(set(pick)) == len(pick)
    assert sum(fin[i][1] for i in pick) >= 60
    assert R.pick_sample(fin, 4, 60, 8) == pick
