"""Sharded serving: ShardedBlockPool bookkeeping + mesh engine parity.

The pool tests are pure host-side bookkeeping and run anywhere.  The
engine tests are marked ``mesh`` — CI runs them with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see ci.yml);
they skip when fewer simulated devices are available because the (2, 4)
host mesh cannot be built.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.paged_cache import ShardedBlockPool


def _pool(shards=2, per_shard=5, block_size=4):
    # slots 0..N map to shards round-robin-by-half: slot // (per-shard
    # slots) — tests use contiguous slot groups like the scheduler does
    return ShardedBlockPool(shards, per_shard, block_size,
                            shard_of=lambda slot: slot // 4)


class TestShardedBlockPool:
    def test_global_ids_are_shard_offset(self):
        p = _pool()
        # slot 0 -> shard 0: local ids 1.. -> global 1..
        assert p.alloc(0, 2) == [1, 2]
        # slot 4 -> shard 1: local ids 1.. -> global per_shard+1..
        assert p.alloc(4, 2) == [6, 7]
        assert p.used == 4

    def test_per_shard_traps(self):
        p = _pool()
        assert p.trap(0) == 0
        assert p.trap(4) == 5   # shard 1's range starts at per_shard

    def test_can_alloc_is_shard_scoped(self):
        p = _pool()            # 4 usable per shard
        p.alloc(0, 4)
        assert not p.can_alloc(1, owner=0)    # shard 0 full
        assert p.can_alloc(4, owner=4)        # shard 1 untouched
        # ownerless query answers for every shard (admission pre-check)
        assert not p.can_alloc(1)

    def test_usable_is_per_shard(self):
        assert _pool(per_shard=5).usable() == 4

    def test_alloc_exhaustion_raises(self):
        p = _pool()
        p.alloc(0, 4)
        with pytest.raises(RuntimeError, match="exhausted"):
            p.alloc(1, 1)      # slot 1 is also shard 0

    def test_share_within_shard_bumps_refcount(self):
        p = _pool()
        blocks = p.alloc(0, 2)
        p.share(1, blocks)     # slot 1 is shard 0 too
        assert p.refcount(blocks[0]) == 2
        assert p.free(0) == []            # still referenced by slot 1
        assert sorted(p.free(1)) == blocks

    def test_cross_shard_share_refused(self):
        p = _pool()
        blocks = p.alloc(0, 1)
        with pytest.raises(RuntimeError, match="cross-shard"):
            p.share(4, blocks)  # slot 4 lives on shard 1

    def test_fork_returns_global_id_in_same_shard(self):
        p = _pool()
        blocks = p.alloc(4, 1)        # shard 1: global id 6
        p.share(5, blocks)
        new = p.fork(5, blocks[0])
        assert new != blocks[0]
        assert new // 5 == 1          # stays in shard 1's range
        assert p.refcount(blocks[0]) == 1

    def test_used_and_peak_aggregate_shards(self):
        p = _pool()
        p.alloc(0, 3)
        p.alloc(4, 2)
        assert p.used == 5
        p.free(0)
        assert p.used == 2
        assert p.peak_used == 5


# ---------------------------------------------------------------- engine
@pytest.mark.mesh
class TestMeshEngine:
    # class-scoped so it orders BEFORE the class-scoped `served` fixture
    # (pytest instantiates higher/equal-scope autouse fixtures first);
    # a function-scoped guard would let `served` build the mesh and
    # error out instead of skipping on single-device runs
    @pytest.fixture(autouse=True, scope="class")
    def _need_devices(self):
        import jax
        if jax.device_count() < 8:
            pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_"
                        "device_count=8")

    @pytest.fixture(scope="class")
    def served(self):
        import jax

        from repro.configs import get_config
        from repro.core.policy import SpeculativePolicy
        from repro.core.scheduler import BatchedEngine
        from repro.data import SyntheticLM
        from repro.launch.mesh import make_host_mesh
        from repro.models import Model

        e_cfg = get_config("smollm-135m").reduced()
        c_cfg = get_config("granite-8b").reduced().replace(
            vocab_size=e_cfg.vocab_size)
        edge, cloud = Model(e_cfg), Model(c_cfg)
        ep = edge.init(jax.random.PRNGKey(0))
        cp = cloud.init(jax.random.PRNGKey(1))
        synth = SyntheticLM(e_cfg.vocab_size)
        rng = np.random.default_rng(0)
        prompts = [synth.sample(rng, i % synth.n_domains, 8)
                   for i in range(8)]

        def serve(mesh):
            eng = BatchedEngine(edge, cloud, batch_size=8, temperature=0.0,
                                use_cache=False,
                                policy=SpeculativePolicy(-1.0),
                                kv_layout="paged", mesh=mesh)
            tr = eng.serve_batch(ep, cp, prompts, 6)
            return [t.tokens for t in tr], eng.stats()

        base, st0 = serve(None)
        mesh_toks, st1 = serve(make_host_mesh(data=2, model=4))
        return base, st0, mesh_toks, st1

    def test_token_parity_with_single_device(self, served):
        base, _, mesh_toks, _ = served
        assert base == mesh_toks

    def test_kv_capacity_scales(self, served):
        _, st0, _, st1 = served
        assert st0["kv_shards"] == 1
        assert st1["kv_shards"] > 1
        assert st1["kv_capacity_blocks"] > st0["kv_capacity_blocks"]

    def test_mesh_stats_reported(self, served):
        _, st0, _, st1 = served
        assert "mesh_devices" not in st0
        assert st1["mesh_devices"] == 8
        assert st1["mesh_shape"] == {"data": 2, "model": 4}

    def test_gather_wave_tiles_dp_dim(self):
        import jax
        import jax.numpy as jnp

        from repro import runtime
        from repro.launch.mesh import make_host_mesh

        x = jnp.arange(8, dtype=jnp.int32).reshape(4, 2)
        # identity off-mesh (single-array calls return the bare array)
        y = runtime.gather_wave(x)
        assert (np.asarray(y) == np.asarray(x)).all()
        mesh = make_host_mesh(data=2, model=4)
        with runtime.mesh_context(mesh):
            xs = jax.device_put(x, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")))
            y, y2 = runtime.gather_wave(xs, xs + 1)
            # all-gather is a reorder-free concat over the dp axis here
            assert (np.asarray(y) == np.asarray(x)).all()
            assert (np.asarray(y2) == np.asarray(x) + 1).all()
            # odd leading dim: identity fallback (cannot tile over dp=2)
            z = jnp.ones((3, 2))
            w = runtime.gather_wave(z)
            assert w.shape == (3, 2)


# ---------------------------------------------------------------- 4 devices
_FOUR_DEVICE_SERVE = r"""
import jax
import numpy as np
from repro.configs import get_config
from repro.core.policy import SpeculativePolicy
from repro.core.scheduler import BatchedEngine
from repro.data import SyntheticLM
from repro.launch.mesh import parse_mesh_arg
from repro.models import Model

assert jax.device_count() == 4, jax.devices()
e_cfg = get_config("smollm-135m").reduced()
c_cfg = get_config("granite-8b").reduced().replace(vocab_size=e_cfg.vocab_size)
edge, cloud = Model(e_cfg), Model(c_cfg)
ep, cp = edge.init(jax.random.PRNGKey(0)), cloud.init(jax.random.PRNGKey(1))
synth = SyntheticLM(e_cfg.vocab_size)
rng = np.random.default_rng(0)
prompts = [synth.sample(rng, i % synth.n_domains, 8) for i in range(8)]


def serve(mesh):
    eng = BatchedEngine(edge, cloud, batch_size=8, temperature=0.0,
                        use_cache=False, policy=SpeculativePolicy(-1.0),
                        kv_layout="paged", mesh=mesh)
    return [t.tokens for t in eng.serve_batch(ep, cp, prompts, 6)]


mesh = parse_mesh_arg("data,model")
print("MESH", dict(mesh.shape))
print("PARITY", serve(None) == serve(mesh))
"""


def _run_on_four_devices(script: str) -> str:
    """Run ``script`` in a child process that owns 4 virtual CPU devices;
    returns its stdout."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_four_device_mesh_serving_token_parity():
    """The mesh path on 4 virtual CPU devices: a (2, 2) data x model mesh
    serves escalated requests token-identically to the single-device
    engine."""
    out = _run_on_four_devices(_FOUR_DEVICE_SERVE)
    assert "MESH {'data': 2, 'model': 2}" in out, out
    assert "PARITY True" in out, out


_FOUR_DEVICE_KERNELS = r"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import runtime
from repro.configs import get_config
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import paged_cache_shardings, params_shardings
from repro.models import Model, layers

cfg = get_config("smollm-135m").reduced()
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
B, BS, MB = 4, 16, 4
NB = B * MB + 2                       # even: the block dim splits over data
rng = np.random.default_rng(0)
cache = model.init_paged_cache(NB, BS, B, MB)
kk, kv, kx = jax.random.split(jax.random.PRNGKey(1), 3)
cache["k"] = jax.random.normal(kk, cache["k"].shape, cache["k"].dtype)
cache["v"] = jax.random.normal(kv, cache["v"].shape, cache["v"].dtype)
cache["table"] = jnp.asarray(
    1 + rng.permutation(NB - 1)[:B * MB].reshape(B, MB), jnp.int32)
cache["pos"] = jnp.asarray([5, 16, 31, 62], jnp.int32)
tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
mesh = make_host_mesh(2, 2)


def step(p, t, c):
    return model.paged_decode_step(p, t, c, attn_backend="kernel")


def err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


one = jax.jit(step)(params, tok, cache)
pm = jax.device_put(params, params_shardings(params, mesh, cfg))
cm = jax.device_put(cache, paged_cache_shardings(cache, mesh, cfg, 2))
with runtime.mesh_context(mesh):
    print("AXES", runtime.kernel_axes(B, cfg.num_kv_heads))
    jaxpr = str(jax.make_jaxpr(step)(pm, tok, cm))
    two = jax.jit(step)(pm, tok, cm)
print("DECODE_SHARD_MAP", "shard_map" in jaxpr and "pallas_call" in jaxpr)
print("DECODE_ERR", max(err(two[0], one[0]), err(two[1]["k"], one[1]["k"]),
                        err(two[1]["v"], one[1]["v"])))

# the tree-verify read: extend_attention's TPU branch, forced here with
# the kernel in interpret mode
tree_kernel = ops._tree
ops._tree = lambda *a, interpret, **kw: tree_kernel(*a, interpret=True, **kw)
ops.on_cpu = lambda: False
T, S = 4, 64
lp = jax.tree.map(lambda x: x[0], params["blocks"]["attn"])
x = jax.random.normal(kx, (B, T, cfg.d_model), jnp.float32)
ck = jax.random.normal(kk, (B, S, cfg.num_kv_heads, cfg.head_dim))
cv = jax.random.normal(kv, (B, S, cfg.num_kv_heads, cfg.head_dim))
# a token tree: each node sees itself and a random subset of earlier nodes
mask = jnp.asarray(np.tril((rng.random((T, T)) < 0.6) | np.eye(T, dtype=bool)))


def verify(p, x, ck, cv):
    return layers.extend_attention(p, x, ck, cv, jnp.int32(20), cfg,
                                   block_mask=mask,
                                   q_positions=20 + jnp.arange(T))[0]


one = jax.jit(verify)(lp, x, ck, cv)
with runtime.mesh_context(mesh):
    args = (jax.device_put(x, NamedSharding(mesh, P("data"))),
            jax.device_put(ck, NamedSharding(mesh, P("data", None, "model"))),
            jax.device_put(cv, NamedSharding(mesh, P("data", None, "model"))))
    jaxpr = str(jax.make_jaxpr(verify)(lp, *args))
    two = jax.jit(verify)(lp, *args)
print("TREE_SHARD_MAP", "shard_map" in jaxpr and "pallas_call" in jaxpr)
print("TREE_ERR", err(two, one))
"""


def test_four_device_sharded_kernels_match_single_device():
    """Both Pallas reads on a (2, 2) mesh run per shard (slots over
    ``data``, kv heads over ``model``, interpret mode on 4 virtual CPU
    devices) and match the single-device result: the paged decode step
    with a data-sharded pool, and the tree-verify attention."""
    out = _run_on_four_devices(_FOUR_DEVICE_KERNELS)
    lines = dict(line.split(" ", 1) for line in out.splitlines())
    assert lines["AXES"] == "(('data',), 'model')", out
    assert lines["DECODE_SHARD_MAP"] == "True", out
    assert lines["TREE_SHARD_MAP"] == "True", out
    # float32: only the order of the sharded sums differs
    assert float(lines["DECODE_ERR"]) < 1e-5, out
    assert float(lines["TREE_ERR"]) < 1e-5, out
