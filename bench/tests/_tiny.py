"""Tiny configurations and mixes that drive the whole harness on the CPU:
the same code path as a chip run, at a size a test can hold."""
import copy

EDGE = {"name": "tiny-edge", "family": "dense", "num_layers": 2,
        "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
        "d_ff": 128, "vocab_size": 512, "mlp_activation": "silu",
        "rope_theta": 10000.0, "norm_eps": 1e-5, "tie_embeddings": True,
        "param_dtype": "bfloat16", "activ_dtype": "bfloat16"}
CLOUD = dict(EDGE, name="tiny-cloud", num_layers=3, d_model=128,
             head_dim=32, d_ff=256)
# limits for these sizes, from tiny sound runs (edge 0.0002-0.003, cloud
# 0.004) and their float8 controls (0.05-0.09)
CFG = {"name": "tiny", "edge": EDGE, "cloud": CLOUD,
       "limits": {"edge_gap": 0.02, "cloud_gap": 0.02}}
CHAT = {"arrival": "poisson", "rate_rps": 2.0, "sizes": 4,
        "pairing_seed": 0, "order_seed": 0,
        "prompt": {"kind": "lognormal", "median": 40, "sigma": 0.8,
                   "min": 20, "max": 80},
        "max_new": {"kind": "lognormal", "median": 8, "sigma": 0.6,
                    "min": 4, "max": 16},
        "escalate_share": 0.0, "escalate_action": "speculative",
        "spec_mode": "linear", "batch": 4}
ESC = dict(CHAT, escalate_share=1.0)
CELLS = {"chat": ("L16-chat-edge", CHAT), "esc": ("L16-escalate", ESC)}


def _cpu(n: int) -> dict:
    """``harness.require_chips`` without the look for a chip."""
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def session(kind: str, seed: int, cfg: dict = CFG, spec: dict = None):
    """A harness ``Session`` on the tiny configuration (or ``cfg``), set up
    and warmed, with the look for a chip left out."""
    from unittest import mock

    from bench import harness as H
    name, mix = CELLS[kind]
    cell = {"name": name, "chips": 1}
    with mock.patch.object(H, "require_chips", _cpu):
        s = H.Session(name, cell, copy.deepcopy(cfg), copy.deepcopy(mix),
                      spec or H.load_spec(), seed=seed)
    s.warm()
    return s


def run(kind: str, seed: int, control: bool = False, seconds: float = 3.0):
    from unittest import mock

    from bench import harness as H
    name, mix = CELLS[kind]
    spec = H.load_spec()
    cell = {"name": name, "chips": 1}
    with mock.patch.object(H, "require_chips", _cpu):
        return H.run_cell(name, cell, copy.deepcopy(CFG),
                          copy.deepcopy(mix), spec, seed=seed,
                          seconds=seconds, trace=False, control=control)
