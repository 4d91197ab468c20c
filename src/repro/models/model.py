"""Unified model API over all architecture families.

    m = Model(cfg)
    params = m.init(rng)
    logits, aux = m.forward(params, batch)          # teacher-forced
    loss = m.loss(params, batch)
    logits, cache = m.prefill(params, batch)
    logits, cache = m.decode_step(params, token, cache)

``batch`` is a dict: {"tokens", "labels"?, "frames"? (encdec stub),
"embeds"? (vlm stub)}.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import (encdec, granite_hybrid, hybrid, ssm, transformer,
                          xlstm)


def cross_entropy(logits, labels, ignore: int = -1):
    """logits (B,S,V) f32; labels (B,S) int32. Mean over non-ignored."""
    mask = (labels != ignore)
    lab = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---------------------------------------------------------------- init
    def init(self, rng):
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.init_params(rng, cfg)
        if cfg.family == "ssm":
            return ssm.init_params(rng, cfg)
        if cfg.family == "xlstm":
            return xlstm.init_params(rng, cfg)
        if cfg.family == "hybrid":
            return hybrid.init_params(rng, cfg)
        if cfg.family == "granitehybrid":
            return granite_hybrid.init_params(rng, cfg)
        if cfg.family == "encdec":
            return encdec.init_params(rng, cfg)
        raise ValueError(cfg.family)

    # ---------------------------------------------------------------- fwd
    def forward(self, params, batch: Dict, *, window: int = 0,
                remat: bool = False, collect_hidden: bool = False):
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.family in ("dense", "moe"):
            return transformer.forward(params, tokens, cfg, window=window,
                                       remat=remat, collect_hidden=collect_hidden)
        if cfg.family == "vlm":
            return transformer.forward(params, tokens, cfg, embeds=batch["embeds"],
                                       window=window, remat=remat,
                                       collect_hidden=collect_hidden)
        if cfg.family == "ssm":
            return ssm.forward(params, tokens, cfg, remat=remat,
                               collect_hidden=collect_hidden)
        if cfg.family == "xlstm":
            return xlstm.forward(params, tokens, cfg, remat=remat,
                                 collect_hidden=collect_hidden)
        if cfg.family == "hybrid":
            return hybrid.forward(params, tokens, cfg, window=window, remat=remat,
                                  collect_hidden=collect_hidden)
        if cfg.family == "granitehybrid":
            return granite_hybrid.forward(params, tokens, cfg, remat=remat,
                                          collect_hidden=collect_hidden)
        if cfg.family == "encdec":
            return encdec.forward(params, tokens, cfg, frames=batch["frames"],
                                  remat=remat, collect_hidden=collect_hidden)
        raise ValueError(cfg.family)

    def loss(self, params, batch: Dict, *, window: int = 0, remat: bool = False):
        out = self.forward(params, batch, window=window, remat=remat)
        logits, aux = out[0], out[1]
        labels = batch["labels"]
        if self.cfg.family == "vlm":
            # image-prefix positions carry no next-token loss
            P = batch["embeds"].shape[1]
            logits = logits[:, P:, :]
        return cross_entropy(logits[:, :-1, :], labels[:, 1:]) + aux

    # ---------------------------------------------------------------- cache
    def init_cache(self, batch_size: int, max_seq: int):
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.init_cache(cfg, batch_size, max_seq)
        if cfg.family == "ssm":
            return ssm.init_cache(cfg, batch_size)
        if cfg.family == "xlstm":
            return xlstm.init_cache(cfg, batch_size)
        if cfg.family == "hybrid":
            return hybrid.init_cache(cfg, batch_size, max_seq)
        if cfg.family == "granitehybrid":
            return granite_hybrid.init_cache(cfg, batch_size, max_seq)
        if cfg.family == "encdec":
            return encdec.init_cache(cfg, batch_size, max_seq)
        raise ValueError(cfg.family)

    def prefill(self, params, batch: Dict, *, max_seq: Optional[int] = None,
                window: int = 0):
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.family in ("dense", "moe"):
            return transformer.prefill(params, tokens, cfg, max_seq=max_seq,
                                       window=window)
        if cfg.family == "vlm":
            return transformer.prefill(params, tokens, cfg, max_seq=max_seq,
                                       embeds=batch["embeds"], window=window)
        if cfg.family == "ssm":
            return ssm.prefill(params, tokens, cfg)
        if cfg.family == "xlstm":
            return xlstm.prefill(params, tokens, cfg)
        if cfg.family == "hybrid":
            return hybrid.prefill(params, tokens, cfg, max_seq=max_seq,
                                  window=window)
        if cfg.family == "granitehybrid":
            return granite_hybrid.prefill(params, tokens, cfg, max_seq=max_seq)
        if cfg.family == "encdec":
            return encdec.prefill(params, tokens, cfg, frames=batch["frames"],
                                  max_seq=max_seq)
        raise ValueError(cfg.family)

    def decode_step(self, params, token, cache, *, window: int = 0):
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.decode_step(params, token, cache, cfg, window=window)
        if cfg.family == "ssm":
            return ssm.decode_step(params, token, cache, cfg)
        if cfg.family == "xlstm":
            return xlstm.decode_step(params, token, cache, cfg)
        if cfg.family == "hybrid":
            return hybrid.decode_step(params, token, cache, cfg, window=window)
        if cfg.family == "granitehybrid":
            return granite_hybrid.decode_step(params, token, cache, cfg)
        if cfg.family == "encdec":
            return encdec.decode_step(params, token, cache, cfg)
        raise ValueError(cfg.family)

    def extend_step(self, params, tokens, cache, *, window: int = 0,
                    block_mask=None, q_positions=None, valid=None):
        """Multi-token cached decode (chunked prefill, speculative verify).
        tokens (B,T) -> (logits (B,T,V), cache).  ``block_mask`` is only
        supported for attention-based decoders (token trees); SSM/hybrid
        recurrences are inherently linear-order.  ``valid`` (traced int,
        ``recurrent_rows`` families): only the first ``valid`` tokens are
        real, the rest padding that leaves the cache as they left it."""
        cfg = self.cfg
        if cfg.family == "granitehybrid":
            if block_mask is not None or q_positions is not None:
                raise ValueError("block_mask unsupported for family "
                                 "'granitehybrid'")
            return granite_hybrid.extend_step(params, tokens, cache, cfg,
                                              valid=valid)
        if valid is not None:
            raise ValueError(f"valid unsupported for family {cfg.family!r}")
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.extend_step(params, tokens, cache, cfg,
                                           window=window, block_mask=block_mask,
                                           q_positions=q_positions)
        if block_mask is not None or q_positions is not None:
            raise ValueError(f"block_mask unsupported for family {cfg.family}")
        if cfg.family == "ssm":
            return ssm.extend_step(params, tokens, cache, cfg)
        if cfg.family == "xlstm":
            return xlstm.extend_step(params, tokens, cache, cfg)
        if cfg.family == "hybrid":
            return hybrid.extend_step(params, tokens, cache, cfg, window=window)
        if cfg.family == "encdec":
            return encdec.extend_step(params, tokens, cache, cfg)
        raise ValueError(cfg.family)

    # ------------------------------------------------------------ paged kv
    @property
    def paged_kv(self) -> bool:
        """True if the family's attention K/V can be laid out as a shared
        block pool + block tables (see ``core/paged_cache.py``): the KV
        transformer families, and granitehybrid, whose attention layers
        page beside a per-slot row of Mamba2 state (``recurrent_rows``).
        Other recurrent state (ssm/hybrid) has no sequence axis to page;
        encdec carries cross-attention K/V pinned to the encoder length."""
        return self.cfg.family in ("dense", "moe", "vlm", "granitehybrid")

    @property
    def recurrent_rows(self) -> bool:
        """True if the paged cache also holds a fixed row of recurrent
        state per slot (``cache["ssm"]``), which the pool's block tables
        do not cover.  Such a family's ``extend_step`` takes ``valid``, so
        that prefill chunks padded to one length leave the state as their
        real tokens left it."""
        return self.cfg.family == "granitehybrid"

    def _require_paged(self):
        if not self.paged_kv:
            raise ValueError(f"paged KV cache unsupported for family "
                             f"{self.cfg.family!r} (KV-cache transformer "
                             "families only)")

    def init_paged_cache(self, num_blocks: int, block_size: int, batch: int,
                         max_blocks: int):
        self._require_paged()
        if self.cfg.family == "granitehybrid":
            return granite_hybrid.init_paged_cache(self.cfg, num_blocks,
                                                   block_size, batch,
                                                   max_blocks)
        return transformer.init_paged_cache(self.cfg, num_blocks, block_size,
                                            batch, max_blocks)

    def paged_decode_step(self, params, token, cache, *,
                          attn_backend: str = "auto", live=None):
        """One decode step over a paged cache. token (B,1) -> (logits (B,V),
        cache).  ``attn_backend``: "auto" (TPU: Pallas paged kernel —
        windowed variant under ``cfg.sliding_window``; CPU: jnp oracle),
        "kernel", "ref", or "gather" (the full-width block-table gather,
        kept only as a test oracle — it is off every decode hot path).
        ``live`` ((B,) bool, ``recurrent_rows`` families): the slots whose
        state rows advance; the others keep theirs."""
        self._require_paged()
        if self.cfg.family == "granitehybrid":
            return granite_hybrid.paged_decode_step(
                params, token, cache, self.cfg, attn_backend=attn_backend,
                live=live)
        if live is not None:
            raise ValueError(f"live unsupported for family "
                             f"{self.cfg.family!r}")
        return transformer.paged_decode_step(params, token, cache, self.cfg,
                                             attn_backend=attn_backend)

    def paged_extend_step(self, params, tokens, cache):
        """Multi-token cached decode over a paged cache. tokens (B,T) ->
        (logits (B,T,V), cache)."""
        self._require_paged()
        if self.cfg.family == "granitehybrid":
            return granite_hybrid.paged_extend_step(params, tokens, cache,
                                                    self.cfg)
        return transformer.paged_extend_step(params, tokens, cache, self.cfg)

    @property
    def rewindable_cache(self) -> bool:
        """True if the cache can be rolled back by resetting ``pos`` (KV
        caches); False for recurrent state (ssm/xlstm/hybrid), which rewinds
        by replaying the accepted prefix (``replay_step``)."""
        return self.cfg.family in ("dense", "moe", "vlm", "encdec")

    def rewind(self, cache, new_pos):
        assert self.rewindable_cache
        return {**cache, "pos": jnp.asarray(new_pos, jnp.int32)}

    def replay_step(self, params, tokens, cache, count):
        """Recurrent-state rewind primitive: re-advance ``cache`` through
        ``tokens[:, :count]`` of a padded draft tape (``count`` () int32;
        ``count == 0`` keeps the cache).  vmapped over slots by the serving
        scheduler, this rewinds every slot to its own accepted count in one
        fused scan — the batched replacement for per-request
        snapshot+replay.  KV-cache families rewind via ``rewind`` instead."""
        cfg = self.cfg
        if cfg.family == "ssm":
            return ssm.replay_step(params, tokens, cache, count, cfg)
        if cfg.family == "xlstm":
            return xlstm.replay_step(params, tokens, cache, count, cfg)
        if cfg.family == "hybrid":
            return hybrid.replay_step(params, tokens, cache, count, cfg)
        if cfg.family == "granitehybrid":
            return granite_hybrid.replay_step(params, tokens, cache, count,
                                              cfg)
        raise ValueError(f"replay_step is for recurrent-state families; "
                         f"{cfg.family!r} caches rewind via pos")


# ---------------------------------------------------------------- batches
def example_batch(cfg: ModelConfig, batch: int, seq: int, rng=None,
                  with_labels: bool = True) -> Dict:
    """Concrete random batch matching input_specs layout (smoke tests)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    r1, r2, r3 = jax.random.split(rng, 3)
    out: Dict = {}
    s_text = seq
    if cfg.family == "vlm":
        s_text = max(seq - cfg.num_image_tokens, 8)
        out["embeds"] = jax.random.normal(
            r2, (batch, cfg.num_image_tokens, cfg.d_model),
            dtype=jnp.dtype(cfg.activ_dtype))
    if cfg.family == "encdec":
        out["frames"] = jax.random.normal(
            r2, (batch, cfg.encoder_seq, cfg.d_model),
            dtype=jnp.dtype(cfg.activ_dtype))
    out["tokens"] = jax.random.randint(r1, (batch, s_text), 0, cfg.vocab_size,
                                       dtype=jnp.int32)
    if with_labels:
        out["labels"] = jax.random.randint(r3, (batch, s_text), 0, cfg.vocab_size,
                                           dtype=jnp.int32)
    return out
