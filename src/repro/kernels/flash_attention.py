"""Flash attention (prefill/train) Pallas TPU kernel.

Online-softmax tiling: the q tile (BQ, hd) stays resident in VMEM while kv
tiles (BK, hd) stream through; running max/denominator live in VMEM scratch
across the (sequential) kv grid axis.  Causal and sliding-window masks are
applied from block coordinates.  MXU alignment: BQ/BK/hd multiples of 128
on real TPU (tests use smaller interpret-mode tiles).

Grid: (B, H, Sq//BQ, Sk//BK) — last axis is the arbitrary/sequential one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq: int, bk: int, nk: int, causal: bool, window: int,
            scale: float):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (BQ, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (BK, hd)
    v = v_ref[0, 0].astype(jnp.float32)            # (BK, hd)
    s = (q @ k.T) * scale                           # (BQ, BK)

    iq = pl.program_id(2)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = k_pos <= q_pos
    if window:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]                             # (BQ,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-20)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """q,k,v: (B, H, S, hd) (kv already expanded over GQA groups).
    Returns (B, H, S, hd)."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(hd)

    kern = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                             window=window, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
