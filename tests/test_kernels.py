"""Pallas kernel sweeps: every kernel vs its pure-jnp oracle across
shapes/dtypes (interpret mode on CPU), plus algebraic property tests."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.group_filter_agg import encode_aggregates, encode_predicates

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,dh,bq,bk",
    [
        (1, 128, 4, 4, 64, 128, 128),  # MHA single block
        (2, 256, 8, 2, 64, 128, 128),  # GQA group 4
        (1, 512, 4, 1, 128, 128, 256),  # MQA, rectangular blocks
        (2, 256, 6, 2, 32, 64, 64),  # head_dim 32, 3-way groups
    ],
)
def test_flash_attention_sweep(dtype, b, s, hq, hkv, dh, bq, bk):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dh), dtype)
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), **_tol(dtype)
    )


def test_flash_attention_non_causal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=128)
    exp = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-4, atol=2e-4)


def test_flash_attention_ragged_is_refused():
    """A sequence the blocks do not divide is an error, not a silent oracle."""
    q = jnp.zeros((1, 200, 4, 64), jnp.float32)
    with pytest.raises(ValueError, match="use_pallas=False"):
        ops.flash_attention(q, q, q, causal=True, block_q=128, block_k=128)
    out = ops.flash_attention(q, q, q, causal=True, block_q=128, block_k=128, use_pallas=False)
    assert out.shape == q.shape


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is interpret


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,dh,bk,lens",
    [
        (2, 256, 8, 4, 64, 128, (100, 256)),
        (1, 512, 4, 1, 128, 256, (1,)),  # single valid token
        (3, 128, 6, 2, 32, 64, (128, 64, 17)),
    ],
)
def test_decode_attention_sweep(dtype, b, s, hq, hkv, dh, bk, lens):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dh), dtype)
    kv_len = jnp.asarray(lens, jnp.int32)
    out = ops.decode_attention(q, k, v, kv_len, block_k=bk)
    exp = ref.decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), **_tol(dtype)
    )


def test_decode_attention_ignores_tail():
    """Cache contents past kv_len must not affect the output."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (1, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    kv_len = jnp.array([100], jnp.int32)
    out1 = ops.decode_attention(q, k, v, kv_len, block_k=64)
    k2 = k.at[:, 100:].set(jax.random.normal(ks[3], (1, 156, 2, 64)) * 50)
    out2 = ops.decode_attention(q, k2, v, kv_len, block_k=64)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [(1, 128, 2, 16, 16, 128), (2, 256, 4, 32, 16, 128), (1, 256, 2, 64, 32, 256)],
)
def test_ssd_intra_sweep(b, s, h, p, n, chunk):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    bm = jax.random.normal(ks[1], (b, s, n), jnp.float32) * 0.5
    cm = jax.random.normal(ks[2], (b, s, n), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h), jnp.float32))
    a = -jnp.exp(jnp.linspace(0.0, 1.5, h))
    y, st_ = ops.ssd_intra(x, bm, cm, dt, a, chunk=chunk)
    ye, ste = ops.ssd_intra(x, bm, cm, dt, a, chunk=chunk, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(ste), rtol=2e-4, atol=2e-4)


def test_ssd_chunked_equals_naive_recurrence():
    """The model's full chunked SSD path == a naive O(S) recurrent scan."""
    from repro.models.ssm import ssd_chunked
    from repro.configs.base import get_arch, tiny

    cfg = tiny(get_arch("mamba2-2.7b"), ssm_chunk=8)
    b, s, h, p, n = 2, 32, cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    bm = jax.random.normal(ks[1], (b, s, n), jnp.float32) * 0.3
    cm = jax.random.normal(ks[2], (b, s, n), jnp.float32) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h), jnp.float32))
    a = -jnp.exp(jnp.linspace(0.0, 1.0, h))
    y_chunk, final = ssd_chunked(cfg, x, bm, cm, dt, a)

    # naive recurrence
    def step(state, i):
        decay = jnp.exp(dt[:, i] * a)  # [B,H]
        upd = jnp.einsum("bh,bn,bhp->bhpn", dt[:, i], bm[:, i], x[:, i])
        state = decay[:, :, None, None] * state + upd
        y = jnp.einsum("bn,bhpn->bhp", cm[:, i], state)
        return state, y

    state0 = jnp.zeros((b, h, p, n))
    final_naive, ys = jax.lax.scan(step, state0, jnp.arange(s))
    y_naive = jnp.moveaxis(ys, 0, 1)  # [B,S,H,P]
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_naive), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(final_naive), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "e,c,d,f,bc,bf,bd",
    [(2, 128, 128, 128, 128, 128, 128), (4, 256, 512, 256, 128, 128, 256),
     (8, 128, 256, 384, 64, 128, 128),
     (2, 200, 320, 300, 128, 128, 256)],  # ragged c/d/f: zero-padded to the blocks
)
def test_gmm_sweep(dtype, e, c, d, f, bc, bf, bd):
    ks = jax.random.split(KEY, 2)
    lhs = jax.random.normal(ks[0], (e, c, d), dtype)
    rhs = jax.random.normal(ks[1], (e, d, f), dtype)
    out = ops.gmm(lhs, rhs, block_c=bc, block_f=bf, block_d=bd)
    exp = ref.gmm_ref(lhs, rhs)
    tol = dict(rtol=3e-2, atol=0.5) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(exp, np.float32), **tol)


# ---------------------------------------------------------------------------
@given(
    n=st.sampled_from([4096, 8192, 20000]),
    lo=st.floats(0.0, 0.5),
    width=st.floats(0.01, 0.5),
)
@settings(max_examples=10, deadline=None)
def test_filter_agg_property(n, lo, width):
    """The one-group, two-range, one-product program (the ``filter_agg``
    workload): kernel == oracle == plain numpy, padding included."""
    cols = jax.random.uniform(jax.random.fold_in(KEY, n), (4, n), jnp.float32)
    keys = jnp.zeros((n,), jnp.int32)
    hi = lo + width
    po, pc = encode_predicates([("range", 0, lo, hi), ("range", 1, 0.2, 0.9)])
    ao, ac = encode_aggregates([[("col", 2), ("col", 3)]])
    out = ops.group_filter_agg_multi(cols, keys, po, pc[None], ao, ac[None],
                                     num_groups=1, block_n=4096)[0]
    exp = ref.group_filter_agg_ref(cols, keys, po, pc, ao, ac, 1)
    assert out.shape == (1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-4, atol=1e-4)
    c = np.asarray(cols, np.float64)
    mask = (c[0] >= lo) & (c[0] < hi) & (c[1] >= 0.2) & (c[1] < 0.9)
    assert int(out[0, 1]) == int(mask.sum())
    np.testing.assert_allclose(float(out[0, 0]), (c[2] * c[3])[mask].sum(), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# group_filter_agg: the generalized single-pass grouped filter+aggregate.
def _gfa_case(n, num_groups, lo, width, seed):
    cols = jax.random.uniform(jax.random.fold_in(KEY, seed), (5, n), jnp.float32)
    keys = jax.random.randint(jax.random.fold_in(KEY, seed + 1), (n,), 0, num_groups)
    pred_ops, pred_consts = encode_predicates(
        [("range", 0, lo, lo + width), ("lt", 1, 2)]
    )
    agg_ops, agg_consts = encode_aggregates(
        [
            [("col", 3)],
            [("col", 3), ("one_minus", 4)],
            [("col", 3), ("one_minus", 4), ("one_plus", 2)],
            [("le", 1, 0.5)],
            [("gt", 1, 0.5)],
        ]
    )
    return cols, keys, pred_ops, pred_consts, agg_ops, agg_consts


@given(
    n=st.sampled_from([512, 4096, 20000, 100_000]),  # ragged tails force padding
    num_groups=st.sampled_from([1, 6, 128]),
    lo=st.floats(0.0, 0.5),
    width=st.floats(0.01, 0.5),
)
@settings(max_examples=10, deadline=None)
def test_group_filter_agg_property(n, num_groups, lo, width):
    """Kernel == oracle == numpy across group counts, predicates, padding."""
    cols, keys, po, pc, ao, ac = _gfa_case(n, num_groups, lo, width, n + num_groups)
    out = ops.group_filter_agg_multi(cols, keys, po, pc[None], ao, ac[None],
                                     num_groups=num_groups, block_n=4096)[0]
    exp = ref.group_filter_agg_ref(cols, keys, po, pc, ao, ac, num_groups)
    assert out.shape == (num_groups, 6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=1e-3)
    # counts are integer sums: exact, and cross-checked against plain numpy
    c, k = np.asarray(cols), np.asarray(keys)
    m = (c[0] >= lo) & (c[0] < lo + width) & (c[1] < c[2])
    np.testing.assert_array_equal(np.asarray(exp[:, -1]), np.asarray(out[:, -1]))
    for g in range(num_groups):
        assert int(out[g, -1]) == int(((k == g) & m).sum())


@pytest.mark.parametrize("all_pass", [True, False])
def test_group_filter_agg_degenerate_masks(all_pass):
    """All-pass (open range) and all-fail (empty range) predicate programs."""
    n = 5000  # ragged vs block 4096
    cols = jax.random.uniform(jax.random.fold_in(KEY, 33), (3, n), jnp.float32)
    keys = jax.random.randint(jax.random.fold_in(KEY, 34), (n,), 0, 6)
    preds = [("range", 0, None, None)] if all_pass else [("range", 0, 0.5, 0.5)]
    po, pc = encode_predicates(preds)
    ao, ac = encode_aggregates([[("col", 1)], [("col", 1), ("col", 2)]])
    out = ops.group_filter_agg_multi(cols, keys, po, pc[None], ao, ac[None],
                                     num_groups=6, block_n=4096)[0]
    exp = ref.group_filter_agg_ref(cols, keys, po, pc, ao, ac, 6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=1e-4)
    assert int(np.asarray(out[:, -1]).sum()) == (n if all_pass else 0)


def test_group_filter_agg_ref_escape_hatch():
    """use_pallas=False routes to the oracle (modulo jit) — same values."""
    cols, keys, po, pc, ao, ac = _gfa_case(4096, 6, 0.1, 0.6, 77)
    a = ops.group_filter_agg_multi(cols, keys, po, pc[None], ao, ac[None],
                                   num_groups=6, use_pallas=False)[0]
    b = ref.group_filter_agg_ref(cols, keys, po, pc, ao, ac, 6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_encode_program_validation():
    with pytest.raises(ValueError, match="unknown predicate kind"):
        encode_predicates([("ge", 0, 1.0, 2.0)])
    with pytest.raises(ValueError, match="unknown term kind"):
        encode_aggregates([[("sqrt", 0)]])
    with pytest.raises(ValueError, match="terms"):
        encode_aggregates([[("col", 0)] * 4])
    po, pc = encode_predicates([])  # empty program = always-true
    assert po.shape == (1, 3) and pc.shape == (1, 2)


# ---------------------------------------------------------------------------
# block_compact: fused capacity-bounded row compaction.
@given(
    n=st.sampled_from([512, 2048, 5000, 20000]),  # ragged tails force padding
    sel=st.floats(0.0, 1.0),
    cap_slack=st.floats(0.25, 2.0),  # caps below AND above the true count
)
@settings(max_examples=10, deadline=None)
def test_block_compact_property(n, sel, cap_slack):
    """Kernel == oracle bit-for-bit, including capacity overflow."""
    k = jax.random.fold_in(KEY, n + int(100 * sel))
    cols = jax.random.uniform(k, (4, n), jnp.float32)
    mask = jax.random.uniform(jax.random.fold_in(k, 1), (n,)) < sel
    cap = max(1, int(cap_slack * max(int(jnp.sum(mask)), 8)))
    out, cnt = ops.block_compact(cols, mask, cap, block_n=2048)
    exp, ecnt = ref.block_compact_ref(cols, mask, cap)
    assert int(cnt) == int(ecnt) == int(np.asarray(mask).sum())
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("fill", [0.0, 1.0])
def test_block_compact_degenerate_masks(fill):
    n = 3000
    cols = jax.random.uniform(jax.random.fold_in(KEY, 55), (3, n), jnp.float32)
    mask = jnp.full((n,), bool(fill))
    out, cnt = ops.block_compact(cols, mask, 1024, block_n=1024)
    exp, ecnt = ref.block_compact_ref(cols, mask, 1024)
    assert int(cnt) == int(ecnt) == (n if fill else 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_block_compact_keeps_zero_valued_rows():
    """Zero-valued qualifying rows are data, not padding: they must survive
    compaction at their slot (the pushdown bug this PR fixes assumed
    value != 0 implied validity)."""
    n = 1024
    cols = jnp.stack([jnp.zeros((n,)), jnp.arange(n, dtype=jnp.float32)])
    mask = jnp.arange(n) % 3 == 0
    cap = int(np.asarray(mask).sum()) + 16
    out, cnt = ops.block_compact(cols, mask, cap, block_n=512)
    exp, ecnt = ref.block_compact_ref(cols, mask, cap)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))
    # row 0 qualifies and is all-zero in col 0; it still occupies slot 0
    assert int(cnt) == int(ecnt)
    assert float(out[1, 0]) == 0.0 and float(out[1, 1]) == 3.0


# ---------------------------------------------------------------------------
# block_compact streaming variant: HBM-resident output, double-buffered DMA.
def _stream_case(n, sel, cap, seed, c=4, **kw):
    k = jax.random.fold_in(KEY, seed)
    cols = jax.random.normal(k, (c, n), jnp.float32)
    mask = jax.random.uniform(jax.random.fold_in(k, 1), (1, n)) < sel
    out, cnt = block_compact_stream(
        cols, mask.astype(jnp.int32), cap, interpret=True, **kw
    )
    exp, ecnt = ref.block_compact_ref(cols, mask, cap)
    assert int(cnt) == int(ecnt), (n, sel, cap)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))
    return cols, mask


from repro.kernels.block_compact import (  # noqa: E402 - grouped with its tests
    _PER_STEP,
    SUB,
    block_compact_stream,
    stream_chunk,
    stream_finalize,
    stream_init,
)


def test_stream_matches_oracle_below_and_above_vmem_bound():
    """Bit-for-bit oracle equality on both sides of the resident kernel's
    capacity ceiling (VMEM_BUDGET_BYTES / 16 rows at 4 columns)."""
    bound = ops.VMEM_BUDGET_BYTES // 16
    _stream_case(65536, 0.4, bound // 4, seed=11, block_n=8192)
    _stream_case(65536, 0.4, bound * 2, seed=12, block_n=8192)


def test_stream_runs_at_4m_cap():
    """The acceptance bar: cap >= 4M rows (output far past the 8 MB VMEM
    budget) streams byte-identically to the oracle."""
    cap = 4 * 1024 * 1024
    assert ops.resident_bytes(4, cap) > ops.VMEM_BUDGET_BYTES
    _stream_case(65536, 0.9, cap, seed=13, block_n=16384)


def test_stream_overflow_clamps_at_cap_boundary():
    """Counts past cap are dropped exactly like nonzero(size=cap): sweep
    caps straddling the qualifying count, including mid-sub-tile caps."""
    n = 16384
    for cap in (100, SUB, SUB + 1, 3 * SUB - 7, 8000):
        _stream_case(n, 0.5, cap, seed=cap, block_n=4096)


def test_stream_ragged_carry_flush():
    """Counts engineered to straddle SUB-tile slots: the carry buffer must
    flush exactly when it fills and the epilogue must place the ragged
    tail at the right offset."""
    n = 8192
    for count in (SUB - 1, SUB, SUB + 1, 2 * SUB - 1, 2 * SUB + 3, 5 * SUB):
        cols = jax.random.normal(jax.random.fold_in(KEY, count), (4, n), jnp.float32)
        mask = (jnp.arange(n) < count).astype(jnp.int32).reshape(1, -1)
        out, cnt = block_compact_stream(cols, mask, 4096, block_n=2048, interpret=True)
        exp, ecnt = ref.block_compact_ref(cols, mask, 4096)
        assert int(cnt) == int(ecnt) == count
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_stream_empty_and_all_pass_blocks():
    """Whole grid blocks with zero qualifiers (no emission at all) and
    all-qualifier blocks (an emission every sub-tile), plus alternating
    full/empty blocks."""
    n = 8192
    _stream_case(n, 0.0, 2048, seed=21, block_n=2048)
    _stream_case(n, 1.0, n, seed=22, block_n=2048)
    cols = jax.random.normal(jax.random.fold_in(KEY, 23), (4, n), jnp.float32)
    mask = ((jnp.arange(n) // 2048) % 2 == 0).astype(jnp.int32).reshape(1, -1)
    out, cnt = block_compact_stream(cols, mask, n, block_n=2048, interpret=True)
    exp, ecnt = ref.block_compact_ref(cols, mask, n)
    assert int(cnt) == int(ecnt) == n // 2
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_stream_chunked_driver_equals_single_call():
    """stream_init/chunk/finalize across 4 chunks == one-shot call == the
    dispatcher's chunked path (chunk_n smaller than the input)."""
    n, cap = 8192, 3000
    k = jax.random.fold_in(KEY, 31)
    cols = jax.random.normal(k, (4, n), jnp.float32)
    mask = (jax.random.uniform(jax.random.fold_in(k, 1), (1, n)) < 0.6).astype(jnp.int32)
    state = stream_init(4, cap)
    for i in range(4):
        sl = slice(i * 2048, (i + 1) * 2048)
        state = stream_chunk(
            state, cols[:, sl], mask[:, sl], cap, block_n=1024, interpret=True
        )
    out_c, cnt_c = stream_finalize(state, cap)
    out_s, cnt_s = block_compact_stream(cols, mask, cap, block_n=1024, interpret=True)
    out_d, cnt_d = ops.block_compact(
        cols, mask, cap, stream="always", chunk_n=2048, block_n=1024
    )
    exp, ecnt = ref.block_compact_ref(cols, mask, cap)
    assert int(cnt_c) == int(cnt_s) == int(cnt_d) == int(ecnt)
    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(exp))
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(exp))
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(exp))


def test_auto_dispatch_streams_past_vmem_budget():
    """stream='auto' routes small caps to the resident kernel and big caps
    to the streaming kernel; both agree with the oracle."""
    n = 4096
    k = jax.random.fold_in(KEY, 41)
    cols = jax.random.normal(k, (4, n), jnp.float32)
    mask = (jax.random.uniform(jax.random.fold_in(k, 1), (1, n)) < 0.5).astype(jnp.int32)
    small = 1024  # resident route
    big = ops.VMEM_BUDGET_BYTES // 16 + SUB  # first cap past the budget
    for cap in (small, big):
        out, cnt = ops.block_compact(cols, mask, cap, block_n=2048)
        exp, ecnt = ref.block_compact_ref(cols, mask, cap)
        assert int(cnt) == int(ecnt)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


# ---------------------------------------------------------------------------
# block_compact, bit for bit: the three-plane bf16 pack and the lane-rotation
# placement at the offsets and values where they could go wrong.
# Blocks of one loop iteration and two sub-tiles more, two blocks.
_EXACT_BLOCK = (_PER_STEP + 2) * SUB
_EXACT_N = 2 * _EXACT_BLOCK


def _finite_bits(key, shape):
    """Random f32 bit patterns with 2^-103 <= |x| < 2^127, both signs."""
    k1, k2 = jax.random.split(key)
    exp = jax.random.randint(k1, shape, 127 - 103, 127 + 127).astype(jnp.uint32)
    man = jax.random.bits(k2, shape, jnp.uint32) & jnp.uint32(0x807FFFFF)
    return jax.lax.bitcast_convert_type(man | (exp << 23), jnp.float32)


def _lineitem_values(key, n):
    """The scanned lineitem columns: whole-day dates, integer quantities,
    two-decimal prices and discounts."""
    k = jax.random.split(key, 4)
    days = jax.random.randint(k[0], (n,), 8036, 10561).astype(jnp.float32)
    qty = jax.random.randint(k[1], (n,), 1, 51).astype(jnp.float32)
    price = jax.random.randint(k[2], (n,), 90000, 10494951).astype(jnp.float32) / 100.0
    disc = jax.random.randint(k[3], (n,), 0, 11).astype(jnp.float32) / 100.0
    return jnp.stack([disc, price, qty, days])


def _sub_tile_mask(key, counts, n):
    """``counts[s]`` qualifiers at random rows of sub-tile ``s``; the rest
    of the sub-tiles qualify at random, about half their rows."""
    rows = []
    for s in range(n // SUB):
        k = jax.random.fold_in(key, s)
        if s < len(counts):
            order = jax.random.permutation(k, SUB)
            rows.append(order < counts[s])
        else:
            rows.append(jax.random.uniform(k, (SUB,)) < 0.5)
    return jnp.concatenate(rows).astype(jnp.int32).reshape(1, n)


# name: (qualifiers in the leading sub-tiles, cap or None for all rows, values)
_EXACT_CASES = {
    "fill0": ([0], None, "bits"),  # the second sub-tile starts at offset 0
    "fill1": ([1], None, "bits"),
    "fill127": ([127], None, "bits"),  # resident skew 127
    "fill511": ([SUB - 1], None, "bits"),  # streaming fill SUB - 1
    "full_and_empty": ([SUB, 0, SUB, 5, 0], None, "bits"),
    "cap_in_sub_tile": ([300, 200], 700, "bits"),  # cap ends inside sub-tile 2
    "lineitem": ([], None, "lineitem"),
}


def _run_compact(kernel, cols, mask, cap):
    if kernel == "resident":
        return ops.block_compact(cols, mask, cap, block_n=_EXACT_BLOCK, stream="never")
    if kernel == "stream":
        return block_compact_stream(cols, mask, cap, block_n=_EXACT_BLOCK, interpret=True)
    # chunked: chunks of 3 sub-tiles, so the carry crosses chunk boundaries
    return ops.block_compact(
        cols, mask, cap, block_n=SUB, stream="always", chunk_n=3 * SUB
    )


@pytest.mark.parametrize("kernel", ["resident", "stream", "chunked"])
@pytest.mark.parametrize("case", sorted(_EXACT_CASES))
def test_block_compact_bit_exact(kernel, case):
    """Every slot equals the oracle's bits (int32 views), for offsets at
    the lane and tile edges, empty and full sub-tiles, a cap inside a
    sub-tile, values across the whole exact range and lineitem values."""
    counts, cap, values = _EXACT_CASES[case]
    k = jax.random.fold_in(KEY, 1000 + sorted(_EXACT_CASES).index(case))
    if values == "bits":
        cols = _finite_bits(k, (4, _EXACT_N))
    else:
        cols = _lineitem_values(k, _EXACT_N)
    mask = _sub_tile_mask(jax.random.fold_in(k, 1), counts, _EXACT_N)
    cap = cap or _EXACT_N
    out, cnt = _run_compact(kernel, cols, mask, cap)
    exp, ecnt = ref.block_compact_ref(cols, mask, cap)
    assert int(cnt) == int(ecnt) == int(np.asarray(mask).sum())
    np.testing.assert_array_equal(
        np.asarray(out).view(np.int32), np.asarray(exp).view(np.int32)
    )
