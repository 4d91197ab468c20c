"""Chunked gated-linear-attention / SSD scan Pallas TPU kernel.

The compute core of Mamba2 (zamba2) and mLSTM (xlstm): per chunk of length
Q, an O(Q^2) masked matmul (intra-chunk) plus a rank-N state carry across
chunks.  Chunks ride the sequential grid axis; the (N, P) state, (N,)
normalizer and log-max stabilizer live in VMEM scratch — exactly the
structure of ``repro.models.ssm.gla_chunked`` (the oracle).

Tiling: Q=128 keeps the (Q,Q) decay matrix + (Q,N)+(Q,P) operand tiles in
VMEM; N=P=64..128 aligns the state matmuls to the MXU.

Grid: (B, H, S//Q) with the chunk axis sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, la_ref, li_ref,
            y_ref, den_ref, m_ref,
            S_scr, n_scr, M_scr, *, Q: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        S_scr[...] = jnp.zeros_like(S_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        M_scr[...] = jnp.full_like(M_scr, _NEG)

    q = q_ref[0, 0].astype(jnp.float32)          # (Q, N)
    k = k_ref[0, 0].astype(jnp.float32)          # (Q, N)
    v = v_ref[0, 0].astype(jnp.float32)          # (Q, P)
    la = la_ref[0, 0].astype(jnp.float32)        # (Q,)
    li = li_ref[0, 0].astype(jnp.float32)        # (Q,)

    La = jnp.cumsum(la)                           # (Q,) inclusive
    w = jax.lax.cummax(li - La, axis=0)
    M = M_scr[0, 0]
    m = La + jnp.maximum(M, w)                    # (Q,)

    c_log = La[:, None] - La[None, :] + li[None, :] - m[:, None]
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cmat = jnp.where(tri, jnp.exp(c_log), 0.0)

    scores = q @ k.T                              # (Q, Q)
    sc = scores * cmat
    y = sc @ v                                    # (Q, P)
    den = jnp.sum(sc, axis=1)                     # (Q,)

    coef = jnp.exp(La + M - m)                    # (Q,)
    y = y + (q @ S_scr[...]) * coef[:, None]
    den = den + (q @ n_scr[0]) * coef

    la_sum = La[Q - 1]
    m_new = la_sum + jnp.maximum(M, w[Q - 1])
    z = jnp.exp(la_sum - La + li - m_new)         # (Q,)
    s_scale = jnp.exp(jnp.minimum(la_sum + M - m_new, 0.0))
    S_scr[...] = s_scale * S_scr[...] + k.T @ (v * z[:, None])
    n_scr[0] = s_scale * n_scr[0] + k.T @ z
    M_scr[0, 0] = m_new

    y_ref[0, 0] = y.astype(y_ref.dtype)
    den_ref[0, 0] = den.astype(den_ref.dtype)
    m_ref[0, 0] = m.astype(m_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(q, k, v, log_a, log_i, *, chunk: int = 128,
                   interpret: bool = False):
    """q,k: (B,S,H,N); v: (B,S,H,P); log_a/log_i: (B,S,H).  S % chunk == 0.
    Returns (y_num (B,S,H,P), den (B,S,H), m (B,S,H)) — stabilized, same
    contract as models.ssm.gla_chunked (zero initial state)."""
    B, S, H, N = q.shape
    P = v.shape[-1]
    Q = chunk
    assert S % Q == 0, (S, Q)
    nc = S // Q

    def to_bh(x):      # (B,S,H,*) -> (B,H,S,*)
        return jnp.moveaxis(x, 2, 1)

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    lab, lib = jnp.moveaxis(log_a, 2, 1), jnp.moveaxis(log_i, 2, 1)

    kern = functools.partial(_kernel, Q=Q)
    y, den, m = pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, Q), lambda b, h, c: (b, h, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, Q), lambda b, h, c: (b, h, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, P), jnp.float32),
            pltpu.VMEM((1, N), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qb, kb, vb, lab, lib)

    back = lambda x: jnp.moveaxis(x, 1, 2)        # (B,H,S,*) -> (B,S,H,*)
    return back(y), back(den), back(m)
