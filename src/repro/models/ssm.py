"""State-space sequence mixing: a generic *chunked gated linear attention*
(GLA) engine shared by Mamba2 (SSD) and xLSTM's mLSTM, the Mamba2 block, and
the standalone pure-Mamba2 model (family "ssm", e.g. ``mamba2-370m``).

Recurrence (per batch b, head h):
    S_t = a_t * S_{t-1} + i_t * k_t v_t^T          (N x P matrix state)
    n_t = a_t * n_{t-1} + i_t * k_t                (N normalizer, mLSTM only)
    y_t = q_t^T S_t        [mamba]      or     q_t^T S_t / max(|q_t^T n_t|, e^{-m_t})  [mlstm]

All math is done in log space with a running max stabilizer m_t so that
exp-input-gated mLSTM is stable; the carried state is S~ = S * e^{-M}.
The chunked form (chunk Q) computes intra-chunk terms with an O(Q^2)
masked matmul and carries (S~, n~, M) across chunks with lax.scan — this is
the structure the `ssd_chunk_scan` Pallas kernel mirrors.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init, split

_NEG = -1e30


class GLAState(NamedTuple):
    S: jnp.ndarray     # (B, H, N, P)  stabilized matrix state
    n: jnp.ndarray     # (B, H, N)     stabilized normalizer
    m: jnp.ndarray     # (B, H)        running log-max


def init_gla_state(B: int, H: int, N: int, P: int, dtype=jnp.float32) -> GLAState:
    return GLAState(
        S=jnp.zeros((B, H, N, P), dtype),
        n=jnp.zeros((B, H, N), dtype),
        m=jnp.full((B, H), _NEG, dtype),
    )


def gla_chunked(q, k, v, log_a, log_i, *, chunk: int,
                state: Optional[GLAState] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, GLAState]:
    """q,k: (B,S,H,N); v: (B,S,H,P); log_a/log_i: (B,S,H).

    Returns (y_num (B,S,H,P), den (B,S,H), m (B,S,H), final_state), all f32.
    ``y_num``/``den`` are stabilized by e^{-m}.
    """
    B, S, H, N = q.shape
    P = v.shape[-1]
    Q = min(chunk, S)
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    log_a, log_i = log_a.astype(f32), log_i.astype(f32)
    # Front-pad to a chunk multiple. Pad steps contribute nothing: k=v=0 and
    # log_i=-1e30 kill their state/normalizer contributions; their (garbage
    # but finite) outputs are sliced off below.
    pad = (-S) % Q
    if pad:
        def pf(x, fill=0.0):
            w = [(0, 0)] * x.ndim
            w[1] = (pad, 0)
            return jnp.pad(x, w, constant_values=fill)
        q, k, v = pf(q), pf(k), pf(v)
        log_a, log_i = pf(log_a), pf(log_i, fill=_NEG)
    S_p = S + pad
    nc = S_p // Q

    def to_chunks(x):
        return x.reshape((B, nc, Q) + x.shape[2:]).swapaxes(0, 1)  # (nc,B,Q,...)

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    lac, lic = to_chunks(log_a), to_chunks(log_i)
    if state is None:
        state = init_gla_state(B, H, N, P)

    tri = np.tril(np.ones((Q, Q), np.bool_))  # s <= j

    def body(carry: GLAState, xs):
        q_c, k_c, v_c, la_c, li_c = xs             # (B,Q,H,*)
        St, nt, M = carry
        La = jnp.cumsum(la_c, axis=1)              # (B,Q,H) inclusive
        w = jax.lax.cummax(li_c - La, axis=1)      # (B,Q,H)
        m = La + jnp.maximum(M[:, None, :], w)     # (B,Q,H) per-row log max
        # ---- intra-chunk
        c_log = (La[:, :, None, :] - La[:, None, :, :]
                 + li_c[:, None, :, :] - m[:, :, None, :])     # (B,j,s,H)
        cmat = jnp.where(tri[None, :, :, None], jnp.exp(c_log), 0.0)
        scores = jnp.einsum("bjhn,bshn->bjsh", q_c, k_c)
        y = jnp.einsum("bjsh,bshp->bjhp", scores * cmat, v_c)
        den = jnp.einsum("bjsh->bjh", scores * cmat)
        # ---- inter-chunk (carry-in state)
        coef = jnp.exp(La + M[:, None, :] - m)                 # (B,Q,H)
        y = y + jnp.einsum("bjhn,bhnp->bjhp", q_c, St) * coef[..., None]
        den = den + jnp.einsum("bjhn,bhn->bjh", q_c, nt) * coef
        # ---- carry update
        la_sum = La[:, -1, :]                                   # (B,H)
        m_new = la_sum + jnp.maximum(M, w[:, -1, :])
        z = jnp.exp(la_sum[:, None, :] - La + li_c - m_new[:, None, :])  # (B,Q,H)
        s_scale = jnp.exp(jnp.clip(la_sum + M - m_new, None, 0.0))
        S_new = s_scale[..., None, None] * St + jnp.einsum(
            "bshn,bshp,bsh->bhnp", k_c, v_c, z)
        n_new = s_scale[..., None] * nt + jnp.einsum("bshn,bsh->bhn", k_c, z)
        return GLAState(S_new, n_new, m_new), (y, den, m)

    final, (ys, dens, ms) = jax.lax.scan(body, state, (qc, kc, vc, lac, lic))

    def from_chunks(x):
        y = x.swapaxes(0, 1).reshape((B, S_p) + x.shape[3:])
        return y[:, pad:] if pad else y

    return from_chunks(ys), from_chunks(dens), from_chunks(ms), final


def gla_step(q, k, v, log_a, log_i, state: GLAState
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, GLAState]:
    """Single decode step. q,k: (B,H,N); v: (B,H,P); log_a/log_i: (B,H)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    log_a, log_i = log_a.astype(f32), log_i.astype(f32)
    St, nt, M = state
    m_new = jnp.maximum(M + log_a, log_i)
    sc = jnp.exp(jnp.clip(M + log_a - m_new, None, 0.0))
    ic = jnp.exp(log_i - m_new)
    S_new = sc[..., None, None] * St + ic[..., None, None] * (k[..., :, None] * v[..., None, :])
    n_new = sc[..., None] * nt + ic[..., None] * k
    y = jnp.einsum("bhn,bhnp->bhp", q, S_new)
    den = jnp.einsum("bhn,bhn->bh", q, n_new)
    return y, den, m_new, GLAState(S_new, n_new, m_new)


# ------------------------------------------------------------- causal conv1d
def init_conv(rng, channels: int, width: int, dtype):
    return {
        "w": dense_init(rng, (width, channels), scale=1.0, dtype=dtype),
        "b": jnp.zeros((channels,), dtype),
    }


def causal_conv(p, x, state=None, valid=None):
    """Depthwise causal conv. x: (B,S,C) -> (B,S,C); returns (y, new_state).
    ``state``: (B, W-1, C) trailing inputs from the previous segment (zeros
    at sequence start).  ``valid`` (traced int): only ``x[:, :valid]`` are
    real inputs, the rest padding; the new state ends at the last real
    one."""
    w = p["w"]                       # (W, C)
    W = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(W))
    y = y + p["b"]
    if W > 1 and valid is not None:
        state = jax.lax.dynamic_slice_in_dim(xp, valid, W - 1, axis=1)
    elif W > 1:
        state = xp[:, -(W - 1):, :]   # last W-1 raw inputs
    else:
        state = jnp.zeros((x.shape[0], 0, x.shape[2]), x.dtype)
    return y, state


def causal_conv_step(p, x, state):
    """x: (B,1,C); state: (B,W-1,C). Returns (y (B,1,C), new_state)."""
    w, b = p["w"], p["b"]
    window = jnp.concatenate([state, x], axis=1)      # (B, W, C)
    y = jnp.einsum("bwc,wc->bc", window, w) + b
    return y[:, None, :], window[:, 1:, :]


# ----------------------------------------------------------------- Mamba2
def init_mamba2(rng, cfg, dtype):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    N, P = cfg.ssm_state, cfg.ssm_head_dim
    H = di // P
    r = split(rng, 6)
    return {
        "in_proj": dense_init(r[0], (d, 2 * di + 2 * N + H), dtype=dtype),
        "conv": init_conv(r[1], di + 2 * N, cfg.conv_kernel, dtype),
        "A_log": jnp.zeros((H,), jnp.float32),          # A = -exp(A_log) = -1
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "norm": jnp.zeros((di,), dtype),
        "out_proj": dense_init(r[2], (di, d), dtype=dtype),
    }


def _mamba_dims(cfg):
    di = cfg.ssm_expand * cfg.d_model
    N, P = cfg.ssm_state, cfg.ssm_head_dim
    return di, N, P, di // P


def _mamba_split(p, x, cfg):
    di, N, P, H = _mamba_dims(cfg)
    if "in_proj" not in p:
        # the projection held as its three column blocks (granitehybrid:
        # widths the chip tiles without a relayout of the whole stack)
        return x @ p["in_z"], x @ p["in_xbc"], x @ p["in_dt"]
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
    return z, xbc, dt


def mamba2_forward(p, x, cfg, cache=None, valid=None):
    """x: (B,S,d) -> (y (B,S,d), final GLA state + conv state).
    ``cache``: optional {"gla": GLAState, "conv": (B,W-1,C)} to continue
    from a previous segment (chunked prefill / speculative extension).
    ``valid`` (traced int): only the first ``valid`` tokens are real; the
    padding after them leaves both states as the real tokens left them
    (no decay, no input), so one padded shape serves every length."""
    from repro.models.layers import rmsnorm
    B, S, d = x.shape
    di, N, P, H = _mamba_dims(cfg)
    z, xbc, dt = _mamba_split(p, x, cfg)
    xbc, conv_state = causal_conv(p["conv"], xbc,
                                  state=None if cache is None else cache["conv"],
                                  valid=valid)
    xbc = jax.nn.silu(xbc)
    xs, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
    delta = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    log_a = -jnp.exp(p["A_log"]) * delta
    log_i = jnp.log(delta + 1e-9)
    if valid is not None:
        real = (jnp.arange(S) < valid)[None, :, None]
        log_a = jnp.where(real, log_a, 0.0)
        log_i = jnp.where(real, log_i, _NEG)
    v = xs.reshape(B, S, H, P)
    k = jnp.broadcast_to(Bm[:, :, None, :], (B, S, H, N))
    q = jnp.broadcast_to(Cm[:, :, None, :], (B, S, H, N))
    y, _den, m, st = gla_chunked(q, k, v, log_a, log_i, chunk=cfg.ssm_chunk,
                                 state=None if cache is None else cache["gla"])
    y = y * jnp.exp(m)[..., None]                                    # un-stabilize
    y = y + p["D"][None, None, :, None] * v.astype(jnp.float32)
    y = y.reshape(B, S, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm(y, p["norm"], cfg.norm_eps)
    return y @ p["out_proj"], {"gla": st, "conv": conv_state}


def mamba2_init_cache(cfg, batch: int, dtype=jnp.float32):
    di, N, P, H = _mamba_dims(cfg)
    return {
        "gla": init_gla_state(batch, H, N, P),
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, di + 2 * N), dtype),
    }


def mamba2_step(p, x, cache, cfg):
    """x: (B,1,d). Returns (y (B,1,d), new_cache)."""
    from repro.models.layers import rmsnorm
    B = x.shape[0]
    di, N, P, H = _mamba_dims(cfg)
    z, xbc, dt = _mamba_split(p, x, cfg)
    xbc, conv_state = causal_conv_step(p["conv"], xbc, cache["conv"])
    xbc = jax.nn.silu(xbc)
    xs, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
    delta = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    log_a = -jnp.exp(p["A_log"]) * delta
    log_i = jnp.log(delta + 1e-9)
    v = xs[:, 0].reshape(B, H, P)
    k = jnp.broadcast_to(Bm[:, 0, None, :], (B, H, N))
    q = jnp.broadcast_to(Cm[:, 0, None, :], (B, H, N))
    y, _den, m, st = gla_step(q, k, v, log_a, log_i, cache["gla"])
    y = y * jnp.exp(m)[..., None] + p["D"][None, :, None] * v.astype(jnp.float32)
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm(y, p["norm"], cfg.norm_eps)
    return y @ p["out_proj"], {"gla": st, "conv": conv_state}


# ------------------------------------------------------- standalone model
# Pure-Mamba2 decoder (family "ssm"): embed + L stacked mamba2 blocks
# consumed with lax.scan (one-block-sized HLO, like hybrid.py minus its
# shared attention) + final norm.  The cache is pure recurrent state —
# no sequence axis at all, so decode cost is O(1) in context length.
def init_params(rng, cfg):
    from repro.models import layers as L
    dtype = jnp.dtype(cfg.param_dtype)
    r = L.split(rng, cfg.num_layers + 2)
    blocks = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[init_mamba2(r[i], cfg, dtype)
                            for i in range(cfg.num_layers)])
    return {
        "embed": L.init_embedding(r[-2], cfg.vocab_size, cfg.d_model, dtype),
        "blocks": blocks,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }


def _stacked_cache(cfg, batch: int):
    base = mamba2_init_cache(cfg, batch)
    cache = jax.tree.map(
        lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), base)
    # running-max needs -inf init, not zeros:
    cache["gla"] = GLAState(cache["gla"].S, cache["gla"].n,
                            jnp.full(cache["gla"].m.shape, _NEG, jnp.float32))
    return cache


def init_cache(cfg, batch: int):
    return {"layers": _stacked_cache(cfg, batch),
            "pos": jnp.zeros((), jnp.int32)}


def forward(params, tokens, cfg, *, remat: bool = False,
            collect_hidden: bool = False):
    from repro import runtime
    from repro.models.layers import embed, rmsnorm, unembed
    h = embed(params["embed"], tokens).astype(jnp.dtype(cfg.activ_dtype))

    def body(hh, p):
        hh = runtime.shard_activation(hh)
        out, _st = mamba2_forward(p, hh, cfg)
        hh = hh + out
        return hh, (hh if collect_hidden else jnp.zeros((), hh.dtype))

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    h, hs = jax.lax.scan(body, h, params["blocks"])
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], h)
    if collect_hidden:
        return logits, jnp.float32(0.0), hs
    return logits, jnp.float32(0.0)


def _run_cached(params, tokens_or_token, cache, cfg, block_fn):
    """Shared scan-over-layers driver for prefill/extend/decode: ``block_fn``
    maps (p, h, layer_state) -> (h, new_state)."""
    from repro import runtime
    from repro.models.layers import embed, rmsnorm, unembed
    h = embed(params["embed"], tokens_or_token).astype(
        jnp.dtype(cfg.activ_dtype))

    def body(hh, xs):
        p, st = xs
        hh = runtime.shard_activation(hh)
        out, st = block_fn(p, hh, st)
        return hh + out, st

    h, states = jax.lax.scan(body, h, (params["blocks"], cache))
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return unembed(params["embed"], h), states


def prefill(params, tokens, cfg):
    """Returns (last-token logits (B,V), cache with final recurrent state)."""
    cache = _stacked_cache(cfg, tokens.shape[0])
    logits, states = _run_cached(
        params, tokens, cache, cfg,
        lambda p, hh, st: mamba2_forward(p, hh, cfg, cache=st))
    return logits[:, -1, :], {"layers": states,
                              "pos": jnp.asarray(tokens.shape[1], jnp.int32)}


def extend_step(params, tokens, cache, cfg):
    """Multi-token cached decode. tokens (B,T) -> (logits (B,T,V), cache)."""
    logits, states = _run_cached(
        params, tokens, cache["layers"], cfg,
        lambda p, hh, st: mamba2_forward(p, hh, cfg, cache=st))
    return logits, {"layers": states,
                    "pos": cache["pos"] + jnp.asarray(tokens.shape[1],
                                                      jnp.int32)}


def decode_step(params, token, cache, cfg):
    """One decode step. token (B,1) -> (logits (B,V), cache)."""
    logits, states = _run_cached(
        params, token, cache["layers"], cfg,
        lambda p, hh, st: mamba2_step(p, hh, st, cfg))
    return logits[:, 0, :], {"layers": states, "pos": cache["pos"] + 1}


# ------------------------------------------------------- batched replay
def tree_where(pred, new, old):
    """Per-leaf ``jnp.where`` over two identically-shaped pytrees: ``pred``
    is a scalar (or broadcastable) bool.  The recurrent families' rewind
    primitive — under ``vmap`` the predicate becomes per-slot, so one call
    selects each slot's state at its own accepted count."""
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), new, old)


def replay_step(params, tokens, cache, count, cfg):
    """Batched accepted-prefix replay for speculative rewind (family "ssm").

    Recurrent state cannot be rolled back by a ``pos`` write, so rewinding
    to an accepted draft prefix means re-advancing from the pre-round state.
    ``tokens`` (B, T) is the PADDED tape [pending token, draft_0 ..
    draft_{T-2}]; ``count`` () int32 in [0, T] is how many of those tokens
    are actually committed.  The scan advances the state only while
    ``t < count`` (a ``tree_where`` select), so vmapping over slots replays
    every slot's own accepted prefix in ONE fused scan — no host-side
    per-request snapshot+replay.  ``count == 0`` returns ``cache``
    unchanged (frozen slots keep their snapshot)."""
    def body(carry, xs):
        t, tok = xs
        _, nxt = decode_step(params, tok[:, None], carry, cfg)
        return tree_where(t < count, nxt, carry), None

    T = tokens.shape[1]
    cache, _ = jax.lax.scan(body, cache,
                            (jnp.arange(T, dtype=jnp.int32), tokens.T))
    return cache
