"""Mixture-of-Experts sublayer (olmoe / granite-moe).

Sort-based capacity dispatch (megablox-style, memory O(T*k + E*C*d)) rather
than the one-hot einsum dispatch (O(T*E*C)) — the latter is intractable at
1M tokens x 64 experts.  Under pjit the (E, C, d) buffers are sharded over
the ``model`` axis (expert parallelism); the scatter/gather to/from the
token-sharded layout lowers to all-to-all style collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, split


def init_moe(rng, cfg, dtype):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    r = split(rng, 4)
    return {
        "router": dense_init(r[0], (d, E), dtype=jnp.float32),
        "w_gate": dense_init(r[1], (E, d, f), dtype=dtype),
        "w_up": dense_init(r[2], (E, d, f), dtype=dtype),
        "w_down": dense_init(r[3], (E, f, d), dtype=dtype),
    }


def capacity(tokens: int, cfg) -> int:
    """Per-expert capacity.  Decode/small batches (T <= 4096) get the
    worst-case dropless capacity so serving is exactly consistent with
    per-token routing; large training batches use the Switch-style
    capacity factor (token dropping is part of the training semantics).

    Dropless bound: top-k indices are DISTINCT per token, so one expert can
    receive at most T assignments — C = T, not T*k (perf iteration #6,
    EXPERIMENTS.md §Perf: 8x less padded expert compute at decode)."""
    if tokens <= 4096:
        return tokens
    c = int(tokens * cfg.top_k / cfg.num_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)   # round up to 8


def moe_block(p, x, cfg):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar f32)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)                              # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (T * k)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # ---- dispatch: sort token-expert assignments by expert id
    C = capacity(T, cfg)
    e_flat = expert_idx.reshape(-1)                           # (T*k,)
    order = jnp.argsort(e_flat)                               # stable
    sorted_e = e_flat[order]
    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    starts = jnp.cumsum(counts) - counts                      # (E,)
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)    # E*C = drop slot
    src_tok = order // k                                      # token per slot

    buf = jnp.zeros((E * C, d), x.dtype).at[dest].set(
        xt[src_tok], mode="drop")
    h = buf.reshape(E, C, d)

    # ---- expert computation (batched over experts)
    act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["w_gate"]))
    act = act * jnp.einsum("ecd,edf->ecf", h, p["w_up"])
    out_e = jnp.einsum("ecf,efd->ecd", act, p["w_down"])      # (E, C, d)

    # ---- combine: gather back and weight by (renormalized) gates
    flat = out_e.reshape(E * C, d)
    gathered = jnp.where(keep[:, None], flat.at[dest].get(mode="fill", fill_value=0.0), 0.0)
    w = gate_vals.reshape(-1)[order]                          # (T*k,)
    combined = jnp.zeros((T, d), jnp.float32).at[src_tok].add(
        gathered.astype(jnp.float32) * w[:, None])
    return combined.reshape(B, S, d).astype(x.dtype), aux


def moe_block_sharded(p, x, cfg, mesh, dp_axes, ep_axis: str):
    """Expert-parallel MoE via shard_map (the survey's MoE-based modular
    collaboration, §2.1.2, mapped to a TPU mesh).

    Layout: tokens sharded over ``dp_axes`` (replicated over ``ep_axis``);
    experts sharded over ``ep_axis``; router replicated.  Each device routes
    its LOCAL tokens to its LOCAL experts and the partial outputs are
    ``psum``-ed over the expert axis — the dispatch/combine collective the
    survey's edge<->cloud MoE transfers correspond to.
    """
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    n_ep = mesh.shape[ep_axis]
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    E_local = E // n_ep
    T_local = (B // n_dp) * S
    C = capacity(T_local, cfg)

    def local_fn(router, wg, wu, wd, xl):
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, d)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (T * k)
        aux_local = cfg.router_aux_coef * E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux_local, tuple(dp_axes) + (ep_axis,))

        lo = jax.lax.axis_index(ep_axis) * E_local
        e_flat = expert_idx.reshape(-1)
        order = jnp.argsort(e_flat)
        sorted_e = e_flat[order]
        counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
        mine = (sorted_e >= lo) & (sorted_e < lo + E_local) & (pos_in_e < C)
        dest = jnp.where(mine, (sorted_e - lo) * C + pos_in_e, E_local * C)
        src_tok = order // k

        buf = jnp.zeros((E_local * C, d), xl.dtype).at[dest].set(
            xt[src_tok], mode="drop")
        h = buf.reshape(E_local, C, d)
        act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wg))
        act = act * jnp.einsum("ecd,edf->ecf", h, wu)
        out_e = jnp.einsum("ecf,efd->ecd", act, wd).reshape(E_local * C, d)

        gathered = jnp.where(mine[:, None],
                             out_e.at[dest].get(mode="fill", fill_value=0.0), 0.0)
        w = gate_vals.reshape(-1)[order]
        combined = jnp.zeros((T, d), jnp.float32).at[src_tok].add(
            gathered.astype(jnp.float32) * w[:, None])
        combined = jax.lax.psum(combined, ep_axis)
        return combined.reshape(Bl, Sl, d).astype(xl.dtype), aux

    dp = tuple(dp_axes)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False)
    return fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)


def moe_apply(p, x, cfg):
    """Dispatch: shard_map expert parallelism when a mesh context is active
    and the token count is large (train/prefill); plain dispatch otherwise."""
    from repro import runtime
    mesh = runtime.current_mesh()
    if mesh is not None and x.shape[0] * x.shape[1] >= 4096 \
            and cfg.num_experts % mesh.shape[runtime.model_axis()] == 0:
        return moe_block_sharded(p, x, cfg, mesh, runtime.data_axes(),
                                 runtime.model_axis())
    return moe_block(p, x, cfg)


def moe_block_dense_fallback(p, x, cfg):
    """Reference: every token through every expert (O(E) FLOPs). Used as the
    numerical oracle in tests for the sparse dispatch above."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    logits = (xt.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    act = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w_gate"]))
    act = act * jnp.einsum("td,edf->tef", xt, p["w_up"])
    out_e = jnp.einsum("tef,efd->ted", act, p["w_down"])      # (T, E, d)
    w = jnp.zeros(probs.shape, jnp.float32)
    w = jax.vmap(lambda wi, ii, gi: wi.at[ii].set(gi))(w, expert_idx, gate_vals)
    out = jnp.einsum("ted,te->td", out_e.astype(jnp.float32), w)
    return out.reshape(B, S, d).astype(x.dtype), jnp.float32(0.0)
