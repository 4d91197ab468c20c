"""End-to-end system behaviour: the collaborative engine (survey Fig. 1b)
composing cache -> edge -> escalation, plus the small-mesh distributed
dry-run (subprocess with its own fake device count)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import CollaborativeEngine
from repro.core.policy import SkeletonPolicy, SpeculativePolicy
from repro.core.speculative import autoregressive_baseline
from repro.models import Model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def pair():
    e_cfg = get_config("smollm-135m").reduced()
    c_cfg = get_config("granite-8b").reduced().replace(
        vocab_size=e_cfg.vocab_size)
    edge, cloud = Model(e_cfg), Model(c_cfg)
    return (edge, edge.init(jax.random.PRNGKey(0)),
            cloud, cloud.init(jax.random.PRNGKey(1)))


def test_engine_edge_path(pair):
    edge, ep, cloud, cp = pair
    eng = CollaborativeEngine(edge, cloud, temperature=0.0,
                              policy=SpeculativePolicy(1.1))
    prompt = np.arange(8) % edge.cfg.vocab_size
    tr = eng.serve(ep, cp, prompt, 8)
    assert tr.path == "edge"
    assert tr.cloud_passes == 0


def test_engine_speculative_escalation_lossless(pair):
    edge, ep, cloud, cp = pair
    eng = CollaborativeEngine(edge, cloud, temperature=0.0,
                              policy=SpeculativePolicy(-1.0),
                              use_cache=False)
    prompt = np.arange(8) % edge.cfg.vocab_size
    tr = eng.serve(ep, cp, prompt, 8)
    assert tr.path == "speculative"
    base = autoregressive_baseline(cloud, cp, prompt, 8, temperature=0.0)
    assert tr.tokens == base                     # escalation = cloud quality


def test_engine_cache_hit(pair):
    edge, ep, cloud, cp = pair
    eng = CollaborativeEngine(edge, cloud, temperature=0.0,
                              policy=SpeculativePolicy(1.1), cache_threshold=0.99)
    prompt = np.arange(8) % edge.cfg.vocab_size
    t1 = eng.serve(ep, cp, prompt, 8)
    t2 = eng.serve(ep, cp, prompt, 8)
    assert t2.path == "cache"
    assert t2.tokens == t1.tokens


def test_engine_skeleton_path(pair):
    edge, ep, cloud, cp = pair
    eng = CollaborativeEngine(edge, cloud, temperature=0.0,
                              policy=SkeletonPolicy(-1.0),
                              use_cache=False, skeleton_len=4)
    prompt = np.arange(8) % edge.cfg.vocab_size
    tr = eng.serve(ep, cp, prompt, 8)
    assert tr.path == "skeleton"
    base = autoregressive_baseline(cloud, cp, prompt, 4, temperature=0.0)
    assert tr.tokens[:4] == base                  # cloud skeleton prefix


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess(tmp_path):
    """Sharded lower+compile on a small fake-device mesh — the same code
    path as the production dry-run, in a subprocess so this test session
    keeps its single CPU device."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro import runtime
from repro.configs import get_config
from repro.launch import sharding as SH
from repro.launch.mesh import make_host_mesh
from repro.models import Model
from repro.training.optimizer import AdamW, AdamWState
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = make_host_mesh(data=4, model=2)
cfg = get_config("olmoe-1b-7b").reduced().replace(num_experts=4, top_k=2)
model = Model(cfg)
params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
opt = AdamW()
opt_state = jax.eval_shape(opt.init, params)
batch = {"tokens": jax.ShapeDtypeStruct((8, 4096), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 4096), jnp.int32)}

def step(p, s, b):
    loss, g = jax.value_and_grad(lambda pp: model.loss(pp, b, remat=True))(p)
    p, s, _ = opt.update(g, s, p)
    return p, s, loss

p_sh = SH.params_shardings(params, mesh)
o_sh = AdamWState(m=p_sh, v=p_sh, step=NamedSharding(mesh, P()))
b_sh = SH.batch_shardings(batch, mesh)
with runtime.mesh_context(mesh):
    compiled = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh)).lower(
        params, opt_state, batch).compile()
from repro.launch.hlo_cost import cost_analysis_dict
print("COMPILED_OK", cost_analysis_dict(compiled).get("flops", 0) > 0)
"""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "COMPILED_OK True" in out.stdout, out.stderr[-2000:]


def test_dryrun_results_recorded():
    """If the production sweep has run in this container, every recorded
    combo must be ok or an explicitly documented skip."""
    d = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("production dry-run sweep not executed yet")
    bad = []
    for f in os.listdir(d):
        if not f.endswith(".json"):
            continue
        rec = json.load(open(os.path.join(d, f)))
        if rec["status"] not in ("ok", "skipped"):
            bad.append(f)
    assert not bad, f"failed dry-runs: {bad}"
