"""Model families: one file each, picked by a model entry's ``family``.

``of(model_cfg)`` loads ``bench/families/<family>.py``.  A new family is
one new file there, with no edit elsewhere in ``bench/``.  A family file
defines:

- ``make_on_device(seed, index, cfg)``: the program's parameter pytree of
  model ``index`` under run seed ``seed``, bf16, built on the device in
  one jitted call (``weights.on_device``) from the shared key derivation
  (``weights.model_key``, ``weights._leaf``) under leaf ids of its own;
- ``logits_at(cfg, seed, index, seqs, starts, fp8=False, device=None)``:
  its plain float32 reference (see ``bench/reference.py``), layer by layer
  on weights drawn again from the seed; with ``fp8`` every matmul input
  is rounded to float8, the control;
- ``token_flops(cfg)`` and ``attn_flops(cfg, ctx_sum)``: the matmul FLOPs
  of one token and the attention FLOPs over summed context (``flops.py``,
  ``work.py``);
- ``paged_layers(cfg)``: how many layers run single-token paged decode
  attention once per decode step.
"""
from __future__ import annotations

import importlib
import importlib.util
from pathlib import Path
from typing import List

FAMILY_DIR = Path(__file__).resolve().parent
_HOME = FAMILY_DIR
# each family file is loaded once per path: its jitted functions keep
# their compiled programs for the life of the process
_loaded = {}


def names() -> List[str]:
    """The families present, by file name."""
    return sorted(p.stem for p in FAMILY_DIR.glob("*.py")
                  if not p.stem.startswith("_"))


def of(model_cfg: dict):
    """The family module of a model entry of a configuration file; a
    family with no file is an error that names those present.  A file of
    this package imports as its module (``bench.families.<family>``, the
    one module object that ``from bench.families import dense`` gives);
    a file of a directory put in ``FAMILY_DIR``'s place loads by path."""
    name = model_cfg.get("family")
    if not (isinstance(name, str) and name.isidentifier()
            and not name.startswith("_")
            and (FAMILY_DIR / f"{name}.py").is_file()):
        raise KeyError(f"no model family {name!r} in {FAMILY_DIR}; "
                       f"known: {names()}")
    path = FAMILY_DIR / f"{name}.py"
    mod = _loaded.get(path)
    if mod is None:
        if FAMILY_DIR == _HOME:
            mod = importlib.import_module(f"{__name__}.{name}")
        else:
            spec = importlib.util.spec_from_file_location(
                f"bench_family_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        _loaded[path] = mod
    return mod
