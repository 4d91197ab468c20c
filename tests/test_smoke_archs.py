"""Per-architecture smoke tests (required deliverable f): a REDUCED variant
of each assigned architecture runs one forward + one train step on CPU with
correct shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_archs
from repro.models import Model, example_batch
from repro.training import AdamW, make_train_step

ARCHS = list_archs()


@pytest.fixture(scope="module")
def setups():
    out = {}
    for arch in ARCHS:
        cfg = get_config(arch).reduced()
        m = Model(cfg)
        out[arch] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nan(arch, setups):
    cfg, m, params = setups[arch]
    B, S = 2, 16
    batch = example_batch(cfg, B, S)
    logits, *_ = m.forward(params, batch)
    assert logits.shape[0] == B
    assert logits.shape[-1] == cfg.vocab_size
    assert not bool(jnp.any(jnp.isnan(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch, setups):
    cfg, m, params = setups[arch]
    batch = example_batch(cfg, 2, 16)
    opt = AdamW(lr=1e-3)
    step = make_train_step(m, opt, donate=False)
    p2, st, metrics = step(params, opt.init(params), batch)
    loss = float(metrics["loss"])
    assert loss == loss, "NaN loss"          # not NaN
    assert 0 < loss < 20
    # params actually changed
    delta = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_fields(arch):
    """The full (non-reduced) config matches the assignment exactly."""
    cfg = get_config(arch)
    expected = {
        "mamba2-370m": (48, 1024, 0, 0, 0, 50288),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "granite-8b": (36, 4096, 32, 8, 14336, 49152),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected
    if arch == "olmoe-1b-7b":
        assert (cfg.num_experts, cfg.top_k) == (64, 8)
    if arch == "granite-moe-1b-a400m":
        assert (cfg.num_experts, cfg.top_k) == (32, 8)
    if arch == "zamba2-2.7b":
        assert cfg.ssm_state == 64
    if arch == "mamba2-370m":
        assert (cfg.ssm_state, cfg.ssm_head_dim) == (128, 64)
    if arch == "granite-4.0-h-micro":
        assert [i for i, k in enumerate(cfg.layer_types)
                if k == "attention"] == [5, 15, 25, 35]
        assert (cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_expand,
                cfg.conv_kernel, cfg.head_dim) == (128, 64, 2, 4, 64)
        assert (cfg.embedding_multiplier, cfg.attention_multiplier,
                cfg.residual_multiplier, cfg.logits_scaling) == \
            (12.0, 1 / 64, 0.22, 8.0)
        assert not cfg.use_rope
        assert abs(cfg.n_params() - 3.19e9) < 0.01e9
