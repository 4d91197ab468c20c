"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import _group_blocks


def _rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape)
    return x.astype(dtype)


# ------------------------------------------------------------ flash attn
@pytest.mark.parametrize("B,H,S,hd", [(1, 1, 128, 64), (2, 3, 256, 64),
                                      (1, 2, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, S, hd, dtype):
    q, k, v = (_rand(i, (B, H, S, hd), dtype) for i in range(3))
    o = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_window(window):
    q, k, v = (_rand(i, (1, 2, 256, 64), jnp.float32) for i in range(3))
    o = ops.flash_attention(q, k, v, causal=True, window=window, bq=64, bk=64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)


def test_flash_attention_noncausal():
    q, k, v = (_rand(i, (1, 1, 128, 64), jnp.float32) for i in range(3))
    o = ops.flash_attention(q, k, v, causal=False, bq=64, bk=64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)


# ------------------------------------------------------------ decode attn
@pytest.mark.parametrize("B,Kv,G,S,hd", [(1, 1, 1, 256, 64), (2, 2, 4, 512, 64),
                                         (1, 4, 8, 1024, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, Kv, G, S, hd, dtype):
    q = _rand(0, (B, Kv, G, hd), dtype)
    k = _rand(1, (B, Kv, S, hd), dtype)
    v = _rand(2, (B, Kv, S, hd), dtype)
    length = jnp.asarray(np.random.default_rng(0).integers(1, S + 1, B), jnp.int32)
    o = ops.decode_attention(q, k, v, length, bs=128)
    o_ref = ref.decode_attention_ref(q, k, v, length)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol, rtol=tol)


def test_decode_attention_window():
    q = _rand(0, (2, 2, 2, 64), jnp.float32)
    k = _rand(1, (2, 2, 512, 64), jnp.float32)
    v = _rand(2, (2, 2, 512, 64), jnp.float32)
    length = jnp.asarray([100, 512], jnp.int32)
    o = ops.decode_attention(q, k, v, length, window=64, bs=128)
    o_ref = ref.decode_attention_ref(q, k, v, length, window=64)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)


# ------------------------------------------------------------ tree verify
def _tree_plan(width, gamma):
    from repro.core.tree_speculation import TreePlan, branching_for
    return TreePlan(branching_for(width, gamma))


@pytest.mark.parametrize("B,Kv,G,S,hd", [(1, 1, 1, 256, 64),
                                         (2, 2, 4, 512, 64),
                                         (1, 4, 2, 160, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tree_verify_attention_sweep(B, Kv, G, S, hd, dtype):
    """Tree-verify kernel vs oracle over a real packed ancestor mask,
    per-sequence lengths, and a non-divisible S (160 forces the wrapper's
    masked tail padding at bs=128)."""
    plan = _tree_plan(2, 4)
    N = plan.n_pad
    q = _rand(0, (B, Kv, G, N, hd), dtype)
    k = _rand(1, (B, Kv, S, hd), dtype)
    v = _rand(2, (B, Kv, S, hd), dtype)
    length = jnp.asarray(
        np.random.default_rng(0).integers(1, S - N + 1, B), jnp.int32)
    mask = jnp.asarray(plan.mask)
    q_pos = length[:, None] + jnp.asarray(plan.depths)[None, :]
    o = ops.tree_verify_attention(q, k, v, length, mask, q_pos, bs=128)
    o_ref = ref.tree_verify_attention_ref(q, k, v, length, mask, q_pos)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=tol, rtol=tol)


def test_tree_verify_attention_windowed():
    """Sliding-window masking is depth-correct: node at depth d sees the
    window a linear decode at position length+d would."""
    plan = _tree_plan(2, 4)
    N = plan.n_pad
    B, Kv, G, S, hd = 2, 2, 2, 512, 64
    q = _rand(0, (B, Kv, G, N, hd), jnp.float32)
    k = _rand(1, (B, Kv, S, hd), jnp.float32)
    v = _rand(2, (B, Kv, S, hd), jnp.float32)
    length = jnp.asarray([100, 480], jnp.int32)
    mask = jnp.asarray(plan.mask)
    q_pos = length[:, None] + jnp.asarray(plan.depths)[None, :]
    o = ops.tree_verify_attention(q, k, v, length, mask, q_pos,
                                  window=64, bs=128)
    o_ref = ref.tree_verify_attention_ref(q, k, v, length, mask, q_pos,
                                          window=64)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)


@pytest.mark.parametrize("level", [1, 2, 3])
def test_tree_verify_attention_rectangular_levels(level):
    """Rectangular (T, C) masks — the incremental level-drafting path:
    query only level ``level``'s nodes while the mask's earlier columns
    cover tree rows previous levels already wrote at
    [length-(C-T), length)."""
    plan = _tree_plan(2, 4)
    lo, hi = plan.levels[level]
    T, C = hi - lo, hi
    B, Kv, G, S, hd = 2, 2, 2, 256, 64
    q = _rand(3, (B, Kv, G, T, hd), jnp.float32)
    k = _rand(4, (B, Kv, S, hd), jnp.float32)
    v = _rand(5, (B, Kv, S, hd), jnp.float32)
    base = jnp.asarray([32, 100], jnp.int32)          # tree starts here
    length = base + lo                                # rows [base, base+lo)
    mask = jnp.asarray(plan.mask)[lo:hi, :hi]         # (T, C), C > T
    q_pos = base[:, None] + jnp.asarray(plan.depths)[None, lo:hi]
    o = ops.tree_verify_attention(q, k, v, length, mask, q_pos, bs=128)
    o_ref = ref.tree_verify_attention_ref(q, k, v, length, mask, q_pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)
    # the square path on the same geometry agrees where rows overlap:
    # a full-tree verify with the square mask yields the same outputs for
    # these nodes once the remaining tree rows are masked garbage
    full_mask = jnp.asarray(plan.mask)
    qf = jnp.zeros((B, Kv, G, plan.n_pad, hd)).at[:, :, :, lo:hi].set(q)
    q_pos_f = base[:, None] + jnp.asarray(plan.depths)[None, :]
    of = ops.tree_verify_attention(qf, k, v, base, full_mask, q_pos_f,
                                   bs=128)
    np.testing.assert_allclose(np.asarray(of[:, :, :, lo:hi]),
                               np.asarray(o), atol=1e-5)


# ------------------------------------------------------------ paged decode
# (B, Kv, G, bs, MB, hd) at smollm-135m's and granite-8b's widths, whose
# tables walk in several groups of blocks
SMOLLM, GRANITE = (3, 3, 3, 32, 20, 64), (3, 8, 4, 32, 20, 128)
PAGED_SHAPES = [(1, 1, 1, 16, 4, 64), (3, 2, 4, 16, 8, 64),
                (2, 4, 2, 32, 4, 128), SMOLLM, GRANITE]
_ID = "-".join


def _paged_inputs(B, Kv, G, bs, MB, hd, dtype):
    """q, pools, a shuffled block table (pool block 0 unused) and random
    lengths in [1, MB*bs]."""
    NB = B * MB + 1
    q = _rand(0, (B, Kv, G, hd), dtype)
    k_pool = _rand(1, (NB, bs, Kv, hd), dtype)
    v_pool = _rand(2, (NB, bs, Kv, hd), dtype)
    rng = np.random.default_rng(0)
    table = rng.permutation(np.arange(1, NB))[:B * MB].reshape(B, MB)
    return q, k_pool, v_pool, table, rng.integers(1, MB * bs + 1, B)


@pytest.mark.parametrize("B,Kv,G,bs,MB,hd,lengths", [
    *[pytest.param(*sh, "random", id=_ID(map(str, sh)))
      for sh in PAGED_SHAPES],
    *[pytest.param(*sh, "edges", id=_ID(["edges", *map(str, sh)]))
      for sh in PAGED_SHAPES]])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_sweep(B, Kv, G, bs, MB, hd, lengths, dtype):
    """Paged kernel (block-table scalar-prefetch gather) vs the pure-jnp
    gather-then-dense oracle, with shuffled per-sequence block tables.
    ``edges`` lengths: one key; exactly one whole group of blocks; past
    the table, as a masked slot's runaway ``pos`` reads."""
    q, k_pool, v_pool, table, length = _paged_inputs(B, Kv, G, bs, MB, hd,
                                                     dtype)
    if lengths == "edges":
        P = _group_blocks(bs, Kv, hd, dtype, MB)
        length = np.resize([1, bs * P, MB * bs + 37], B)
    table, length = jnp.asarray(table, jnp.int32), jnp.asarray(length,
                                                               jnp.int32)
    o = ops.paged_decode_attention(q, k_pool, v_pool, table, length)
    o_ref = ref.paged_decode_attention_ref(q, k_pool, v_pool, table, length)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,Kv,G,bs,MB,hd", [SMOLLM, GRANITE])
def test_paged_decode_never_reads_past_length(B, Kv, G, bs, MB, hd):
    """Every pool block but those below each slot's length holds NaN: the
    kernel's output stays finite and equals the oracle on clean pools.  A
    block read and masked would still poison ``p @ v`` (0 x NaN)."""
    B = 4
    q, k_pool, v_pool, table, _ = _paged_inputs(B, Kv, G, bs, MB, hd,
                                                jnp.bfloat16)
    P = _group_blocks(bs, Kv, hd, jnp.bfloat16, MB)
    length = np.asarray([1, bs * P, bs * (P + 1) + 5, MB * bs + 37])
    live = np.concatenate([table[b, :-(-min(n, MB * bs) // bs)]
                           for b, n in enumerate(length)])
    dead = np.setdiff1d(np.arange(k_pool.shape[0]), live)
    table, length = jnp.asarray(table, jnp.int32), jnp.asarray(length,
                                                               jnp.int32)
    o = ops.paged_decode_attention(q, k_pool.at[dead].set(jnp.nan),
                                   v_pool.at[dead].set(jnp.nan), table,
                                   length)
    o_ref = ref.paged_decode_attention_ref(q, k_pool, v_pool, table, length)
    assert np.isfinite(np.asarray(o, np.float32)).all()
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("shape,window", [
    *[pytest.param((4, 2, 4, 8, 6, 64), w, id=str(w))
      for w in (4, 8, 11, 16, 48)],
    *[pytest.param((4, *SMOLLM[1:]), w, id=f"smollm-{w}") for w in (40, 600)]])
def test_paged_decode_attention_windowed(shape, window):
    """Windowed paged kernel (trailing-window blocks only, from the
    scalar-prefetched start block) vs the windowed oracle — unaligned
    windows, lengths below the window (early-position clamp), windows
    past the whole table, and (smollm-135m widths) windows that span
    groups of blocks."""
    B, Kv, G, bs, MB, hd = shape
    q, k_pool, v_pool, table, _ = _paged_inputs(B, Kv, G, bs, MB, hd,
                                                jnp.float32)
    table = jnp.asarray(table, jnp.int32)
    length = jnp.asarray([3, 17 * bs // 8, 30 * bs // 8, MB * bs], jnp.int32)
    o = ops.paged_decode_attention(q, k_pool, v_pool, table, length,
                                   window=window)
    o_ref = ref.paged_decode_attention_ref(q, k_pool, v_pool, table, length,
                                           window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_windowed_matches_full_when_window_covers_length():
    """window >= length degenerates to full attention — the windowed grid
    restriction must not drop any valid block."""
    B, Kv, G, bs, MB, hd = 2, 2, 2, 8, 4, 64
    NB = B * MB + 1
    q = _rand(0, (B, Kv, G, hd), jnp.float32)
    k_pool = _rand(1, (NB, bs, Kv, hd), jnp.float32)
    v_pool = _rand(2, (NB, bs, Kv, hd), jnp.float32)
    table = jnp.asarray(np.arange(1, NB).reshape(B, MB), jnp.int32)
    length = jnp.asarray([7, 29], jnp.int32)
    o_win = ops.paged_decode_attention(q, k_pool, v_pool, table, length,
                                       window=MB * bs)
    o_full = ops.paged_decode_attention(q, k_pool, v_pool, table, length)
    np.testing.assert_allclose(np.asarray(o_win), np.asarray(o_full),
                               atol=1e-5, rtol=1e-5)


def test_paged_matches_dense_on_contiguous_table():
    """With an identity (contiguous) block table the paged kernel computes
    exactly what the dense decode kernel computes over the flat cache."""
    B, Kv, G, bs, MB, hd = 2, 2, 2, 32, 4, 64
    NB = B * MB + 1
    q = _rand(0, (B, Kv, G, hd), jnp.float32)
    k_pool = _rand(1, (NB, bs, Kv, hd), jnp.float32)
    v_pool = _rand(2, (NB, bs, Kv, hd), jnp.float32)
    table = jnp.asarray(np.arange(1, NB).reshape(B, MB), jnp.int32)
    length = jnp.asarray([40, 128], jnp.int32)
    kk = jnp.moveaxis(k_pool[table].reshape(B, -1, Kv, hd), 2, 1)
    vv = jnp.moveaxis(v_pool[table].reshape(B, -1, Kv, hd), 2, 1)
    o_paged = ops.paged_decode_attention(q, k_pool, v_pool, table, length)
    o_dense = ops.decode_attention(q, kk, vv, length, bs=32)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_dense),
                               atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------ dense window
@pytest.mark.parametrize("window", [3, 4, 7])
@pytest.mark.parametrize("use_rope", [False, True])
def test_dense_decode_window_clamp_vs_full_oracle(window, use_rope):
    """Audit of the dense sliding-window decode path
    (``layers.decode_attention``: ``start = max(pos - (window-1), 0)`` +
    ``dynamic_slice_in_dim``): at every position the windowed read must
    equal full attention masked to the trailing ``window`` keys — and at
    ``pos < window`` (where the slice start clamps to 0) it must equal
    UNRESTRICTED full attention exactly.  Parametrized over early, exact-
    boundary, and deep positions; no off-by-one found, test pins it."""
    from types import SimpleNamespace
    from repro.models import layers as L
    cfg = SimpleNamespace(num_heads=4, num_kv_heads=2, head_dim=16,
                          use_rope=use_rope, rope_theta=10_000.0)
    d = 32
    rng = jax.random.PRNGKey(0)
    p = L.init_attention(rng, SimpleNamespace(d_model=d, head_dim=16,
                                              num_heads=4, num_kv_heads=2),
                         jnp.float32)
    Smax = 2 * window + 4
    xs = jax.random.normal(jax.random.PRNGKey(1), (Smax, 1, 1, d))
    ck_w = jnp.zeros((1, Smax, 2, 16))
    cv_w = jnp.zeros_like(ck_w)
    ck_f, cv_f = ck_w, cv_w
    for pos in range(Smax):
        o_w, ck_w, cv_w = L.decode_attention(p, xs[pos], ck_w, cv_w, pos,
                                             cfg, window=window)
        o_f, ck_f, cv_f = L.decode_attention(p, xs[pos], ck_f, cv_f, pos,
                                             cfg, window=0)
        if pos < window:
            # early positions: window covers the whole prefix -> identical
            # to full attention (the clamp must not drop position 0)
            np.testing.assert_allclose(np.asarray(o_w), np.asarray(o_f),
                                       atol=1e-5, rtol=1e-5,
                                       err_msg=f"pos={pos}")
        else:
            # deep positions: equals full attention over the cache masked
            # to keys (pos - window, pos]
            q = (xs[pos] @ p["wq"]).reshape(1, 1, 4, 16)
            kpos = jnp.arange(Smax)
            if use_rope:
                q = L.apply_rope(q, jnp.asarray([pos], jnp.int32),
                                 cfg.rope_theta)
            mask = ((kpos <= pos) & (kpos > pos - window))[None, None, None,
                                                           None, :]
            o_ref = L.mha(q, ck_f, cv_f, mask=mask).reshape(1, 1, 64) \
                @ p["wo"]
            np.testing.assert_allclose(np.asarray(o_w), np.asarray(o_ref),
                                       atol=1e-5, rtol=1e-5,
                                       err_msg=f"pos={pos}")


# ------------------------------------------------------------ spec verify
@pytest.mark.parametrize("gamma,V", [(1, 64), (4, 1000), (8, 4096)])
@pytest.mark.parametrize("temperature", [0.0, 0.7, 1.0])
def test_spec_verify_matches_ref(gamma, V, temperature):
    rng = jax.random.PRNGKey(42)
    tl = _rand(0, (gamma + 1, V), jnp.float32) * 2
    dl = tl[:gamma] + _rand(1, (gamma, V), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (gamma,), 0, V)
    n1, t1 = ops.spec_verify(rng, tl, dl, toks, temperature=temperature)
    n2, t2 = ref.spec_verify_ref(rng, tl, dl, toks, temperature=temperature)
    assert int(n1) == int(n2)
    assert int(t1) == int(t2)


def test_spec_verify_all_accept_identical():
    rng = jax.random.PRNGKey(0)
    tl = _rand(0, (5, 128), jnp.float32)
    n, _ = ops.spec_verify(rng, tl, tl[:4],
                           jnp.argmax(tl[:4], -1).astype(jnp.int32),
                           temperature=0.0)
    assert int(n) == 4


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_spec_verify_batched_matches_per_row(temperature):
    """The grouped entry point equals per-member spec_verify calls."""
    G, gamma, V = 3, 4, 256
    rngs = jax.random.split(jax.random.PRNGKey(7), G)
    tl = _rand(0, (G, gamma + 1, V), jnp.float32) * 2
    dl = tl[:, :gamma] + _rand(1, (G, gamma, V), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (G, gamma), 0, V)
    n_b, t_b = ops.spec_verify_batched(rngs, tl, dl, toks,
                                       temperature=temperature)
    for g in range(G):
        n1, t1 = ops.spec_verify(rngs[g], tl[g], dl[g], toks[g],
                                 temperature=temperature)
        assert int(n_b[g]) == int(n1)
        assert int(t_b[g]) == int(t1)


# ------------------------------------------------------------ ssd scan
@pytest.mark.parametrize("B,S,H,N,P,Q", [(1, 128, 2, 16, 32, 32),
                                         (2, 256, 3, 32, 64, 64),
                                         (1, 512, 1, 64, 64, 128)])
def test_ssd_chunk_scan_sweep(B, S, H, N, P, Q):
    q = _rand(0, (B, S, H, N), jnp.float32)
    k = _rand(1, (B, S, H, N), jnp.float32)
    v = _rand(2, (B, S, H, P), jnp.float32)
    la = -jax.nn.softplus(_rand(3, (B, S, H), jnp.float32))
    li = _rand(4, (B, S, H), jnp.float32) * 0.5
    y1, d1, m1 = ops.ssd_chunk_scan(q, k, v, la, li, chunk=Q)
    y2, d2, m2 = ref.ssd_chunk_scan_ref(q, k, v, la, li, chunk=Q)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-5)


def test_ssd_kernel_vs_sequential_step():
    """Chunked kernel == step-by-step recurrence (chunk-size invariance)."""
    from repro.models.ssm import gla_step, init_gla_state
    B, S, H, N, P = 1, 64, 2, 8, 16
    q = _rand(0, (B, S, H, N), jnp.float32)
    k = _rand(1, (B, S, H, N), jnp.float32)
    v = _rand(2, (B, S, H, P), jnp.float32)
    la = -jax.nn.softplus(_rand(3, (B, S, H), jnp.float32))
    li = _rand(4, (B, S, H), jnp.float32)
    y_k, d_k, m_k = ops.ssd_chunk_scan(q, k, v, la, li, chunk=16)
    st = init_gla_state(B, H, N, P)
    for t in range(S):
        y_t, d_t, m_t, st = gla_step(q[:, t], k[:, t], v[:, t],
                                     la[:, t], li[:, t], st)
        # compare un-stabilized outputs (stabilizers m may differ)
        np.testing.assert_allclose(
            np.asarray(y_t * jnp.exp(m_t)[..., None]),
            np.asarray(y_k[:, t] * jnp.exp(m_k[:, t])[..., None]),
            atol=1e-3, rtol=1e-3)
