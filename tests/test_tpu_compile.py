"""Compile rehearsals for the TPU: the served path's Pallas kernels and one
full-width decode tick, compiled for a described (not attached) v5e chip.

Nothing runs, so these say nothing about results or times; they catch
what the chip's compiler refuses (block tiling, VMEM, partitioning) at no
chip cost.  The topology is described inside a fixture, never at import:
only the worker that runs this file loads the TPU compiler.  JAX's
persistent compilation cache is off around these compiles, since an
entry written for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.tree_attention import tree_verify_attention
from repro.models import Model

B, BLOCK, MAX_BLOCKS = 8, 32, 20          # 8 slots, 640-token block tables


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The model code picks interpret mode from the default backend, which
    here is the CPU: compile its kernels for the described chip instead."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_cpu", lambda: False)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _widths(name):
    cfg = get_config(name)
    return cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim


@pytest.mark.parametrize("name", ["smollm-135m", "granite-8b"])
def test_paged_decode_kernel_compiles(one_chip, name):
    Kv, G, hd = _widths(name)
    nb = B * MAX_BLOCKS + 1
    bf16 = jnp.bfloat16
    hlo = _compiled_text(
        paged_decode_attention,
        _shape((B, Kv, G, hd), bf16, one_chip),
        _shape((nb, BLOCK, Kv, hd), bf16, one_chip),
        _shape((nb, BLOCK, Kv, hd), bf16, one_chip),
        _shape((B, MAX_BLOCKS), jnp.int32, one_chip),
        _shape((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("per_slot", [False, True],
                         ids=["batched", "vmapped_slots"])
def test_tree_verify_kernel_compiles(one_chip, per_slot):
    """Granite-8b widths, a 16-node tree over a 640-entry cache; the
    scheduler's tree lane calls the kernel vmapped over slots."""
    Kv, G, hd = _widths("granite-8b")
    N, S = 16, MAX_BLOCKS * BLOCK
    lead = (B, 1) if per_slot else (B,)
    args = (_shape(lead + (Kv, G, N, hd), jnp.bfloat16, one_chip),
            _shape(lead + (Kv, S, hd), jnp.bfloat16, one_chip),
            _shape(lead + (Kv, S, hd), jnp.bfloat16, one_chip),
            _shape(lead, jnp.int32, one_chip),
            _shape((N, N), jnp.bool_, one_chip),
            _shape(lead + (N,), jnp.int32, one_chip))
    fn = tree_verify_attention
    if per_slot:
        fn = jax.vmap(tree_verify_attention, in_axes=(0, 0, 0, 0, None, 0))
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def _paged_step_args(model, sharding_of):
    """Abstract params and paged cache for one decode step, each leaf
    placed by ``sharding_of(path, leaf)``."""
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        B * MAX_BLOCKS + 1, BLOCK, B, MAX_BLOCKS))

    def place(tree):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: _shape(x.shape, x.dtype, sharding_of(path, x)),
            tree)
    return place(params), place(cache)


def test_smollm_paged_decode_tick_compiles(one_chip, mosaic):
    """One whole decode step of smollm-135m at its published size, kernel
    read forced as on the chip."""
    model = Model(get_config("smollm-135m"))
    params, cache = _paged_step_args(model, lambda path, x: one_chip)
    tok = _shape((B, 1), jnp.int32, one_chip)
    compiled = jax.jit(lambda p, t, c: model.paged_decode_step(
        p, t, c, attn_backend="kernel")).lower(params, tok, cache).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,layers,tensor_parallel", [
    ("granite-8b", 2, True), ("smollm-135m", 30, False)],
    ids=["cloud", "edge"])
def test_paged_decode_step_compiles_on_model_mesh(topo, mosaic, name, layers,
                                                  tensor_parallel):
    """Mesh data=1,model=4 as the engine builds it: the cloud's params
    tensor-parallel (two of granite-8b's layers at full width keep the
    compile short), the edge's replicated.  The kernel runs per shard
    under shard_map: on each chip's kv heads where they divide, else on
    all of them."""
    from repro import runtime
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import paged_cache_spec, param_spec

    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    cfg = get_config(name).replace(num_layers=layers)
    model = Model(cfg)
    params, _ = _paged_step_args(model, lambda path, x: NamedSharding(
        mesh, param_spec(path, x, mesh, cfg) if tensor_parallel else P()))
    _, cache = _paged_step_args(model, lambda path, x: NamedSharding(
        mesh, paged_cache_spec(path, x, mesh, cfg)))
    tok = _shape((B, 1), jnp.int32, NamedSharding(mesh, P()))
    with runtime.mesh_context(mesh):
        compiled = jax.jit(lambda p, t, c: model.paged_decode_step(
            p, t, c, attn_backend="kernel")).lower(
                params, tok, cache).compile()
    assert "tpu_custom_call" in compiled.as_text()
