"""Pure-jnp oracles for every Pallas kernel. The kernels must match these
(assert_allclose in tests/test_kernels.py over shape/dtype sweeps)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
def flash_attention_ref(
    q: jax.Array,  # [B, Sq, Hq, dh]
    k: jax.Array,  # [B, Sk, Hkv, dh]
    v: jax.Array,  # [B, Sk, Hkv, dh]
    *,
    causal: bool = True,
) -> jax.Array:
    """f32 softmax attention with GQA head grouping. Output [B, Sq, Hq, dh]."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    s = s * (dh**-0.5)
    if causal:
        mask = jnp.tril(jnp.ones((sq, k.shape[1]), bool), k.shape[1] - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, dh).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,  # [B, Hq, dh] — one query token per sequence
    k: jax.Array,  # [B, S, Hkv, dh]
    v: jax.Array,  # [B, S, Hkv, dh]
    kv_len: jax.Array,  # [B] int32 — valid cache length per sequence
) -> jax.Array:
    b, hq, dh = q.shape
    s = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, dh)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k, preferred_element_type=jnp.float32)
    scores = scores * (dh**-0.5)
    valid = jnp.arange(s)[None] < kv_len[:, None]  # [B, S]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
def ssd_intra_ref(
    x: jax.Array,  # [B, Q, H, P] — one chunk
    bmat: jax.Array,  # [B, Q, N]
    cmat: jax.Array,  # [B, Q, N]
    dt: jax.Array,  # [B, Q, H] (post-softplus, f32)
    a: jax.Array,  # [H] (negative)
) -> tuple[jax.Array, jax.Array]:
    """Intra-chunk SSD: returns (y [B, Q, H, P] f32, chunk_state [B, H, P, N] f32)."""
    bsz, q, h, p = x.shape
    dta = dt.astype(jnp.float32) * a.astype(jnp.float32)  # [B,Q,H]
    lcum = jnp.cumsum(dta, axis=1)
    l_last = lcum[:, -1]  # [B,H]
    cb = jnp.einsum("bqn,bkn->bqk", cmat, bmat, preferred_element_type=jnp.float32)
    decay = jnp.exp(lcum[:, :, None, :] - lcum[:, None, :, :])  # [B,Q,K,H]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(causal[None, :, :, None], decay, 0.0)
    m = cb[..., None] * decay * dt[:, None, :, :].astype(jnp.float32)  # [B,Q,K,H]
    y = jnp.einsum("bqkh,bkhp->bqhp", m, x.astype(jnp.float32))
    seg = jnp.exp(l_last[:, None, :] - lcum) * dt.astype(jnp.float32)  # [B,Q,H]
    state = jnp.einsum("bkh,bkn,bkhp->bhpn", seg, bmat.astype(jnp.float32), x.astype(jnp.float32))
    return y, state


# ---------------------------------------------------------------------------
def gmm_ref(lhs: jax.Array, rhs: jax.Array) -> jax.Array:
    """Grouped (per-expert) matmul: [E, C, d] x [E, d, f] -> [E, C, f]."""
    return jnp.einsum("ecd,edf->ecf", lhs, rhs, preferred_element_type=jnp.float32).astype(
        lhs.dtype
    )


# ---------------------------------------------------------------------------
def _program_mask(cols: jax.Array, pred_ops: jax.Array, pred_consts: jax.Array) -> jax.Array:
    """Row mask [N] from a group_filter_agg predicate program."""
    n = cols.shape[1]
    mask = jnp.ones((n,), bool)
    for k in range(pred_ops.shape[0]):
        kind, a, b = pred_ops[k, 0], pred_ops[k, 1], pred_ops[k, 2]
        lo, hi = pred_consts[k, 0], pred_consts[k, 1]
        ca = jax.lax.dynamic_index_in_dim(cols, a, 0, keepdims=False)
        cb = jax.lax.dynamic_index_in_dim(cols, b, 0, keepdims=False)
        mask &= jnp.where(kind == 0, (ca >= lo) & (ca < hi), ca < cb)
    return mask


def _program_values(cols: jax.Array, agg_ops: jax.Array, agg_consts: jax.Array) -> jax.Array:
    """Per-row aggregate values [A, N] from a group_filter_agg term program."""
    num_aggs, n = agg_ops.shape[0], cols.shape[1]
    max_terms = agg_consts.shape[1]
    vals = []
    for a in range(num_aggs):
        v = jnp.ones((n,), jnp.float32)
        for t in range(max_terms):
            mode, col = agg_ops[a, 2 * t], agg_ops[a, 2 * t + 1]
            const = agg_consts[a, t]
            c = jax.lax.dynamic_index_in_dim(cols, col, 0, keepdims=False)
            c = c.astype(jnp.float32)
            term = jnp.where(mode == 1, c, 1.0)
            term = jnp.where(mode == 2, 1.0 - c, term)
            term = jnp.where(mode == 3, 1.0 + c, term)
            term = jnp.where(mode == 4, (c <= const).astype(jnp.float32), term)
            term = jnp.where(mode == 5, (c > const).astype(jnp.float32), term)
            v = v * term
        vals.append(v)
    return jnp.stack(vals)


def group_filter_agg_ref(
    cols: jax.Array,  # [C, N] f32
    keys: jax.Array,  # [1, N] or [N] i32 group ids (negative = dropped)
    pred_ops: jax.Array,  # [K, 3] i32 — see kernels/group_filter_agg.py
    pred_consts: jax.Array,  # [K, 2] f32
    agg_ops: jax.Array,  # [A, 2*MAX_TERMS] i32
    agg_consts: jax.Array,  # [A, MAX_TERMS] f32
    num_groups: int,
) -> jax.Array:
    """Fused grouped filter+aggregate oracle.  Returns [G, A + 1] f32:
    per-group masked aggregate sums, then the masked row count."""
    keys = keys.reshape(-1)
    w = _program_mask(cols, pred_ops, pred_consts).astype(jnp.float32)
    # Out-of-range keys (the wrapper's -1 padding) must contribute nothing.
    w = w * ((keys >= 0) & (keys < num_groups)).astype(jnp.float32)
    seg_keys = jnp.clip(keys, 0, num_groups - 1)
    vals = _program_values(cols, agg_ops, agg_consts)
    parts = [
        jax.ops.segment_sum(vals[a] * w, seg_keys, num_segments=num_groups)
        for a in range(agg_ops.shape[0])
    ]
    parts.append(jax.ops.segment_sum(w, seg_keys, num_segments=num_groups))
    return jnp.stack(parts, axis=1)


def group_filter_agg_multi_ref(
    cols: jax.Array,  # [C, N] f32
    keys: jax.Array,  # [1, N] or [N] i32
    pred_ops: jax.Array,  # [K, 3] i32, shared across programs
    pred_consts: jax.Array,  # [B, K, 2] f32 per-program constants
    agg_ops: jax.Array,  # [A, 2*MAX_TERMS] i32, shared
    agg_consts: jax.Array,  # [B, A, MAX_TERMS] f32 per-program constants
    num_groups: int,
) -> jax.Array:
    """Scan-shared multi-program oracle: per program slot, exactly the
    single-program oracle.  Returns [B, G, A + 1] f32."""
    return jnp.stack(
        [
            group_filter_agg_ref(
                cols, keys, pred_ops, pred_consts[b], agg_ops, agg_consts[b], num_groups
            )
            for b in range(pred_consts.shape[0])
        ]
    )


def block_compact_ref(
    cols: jax.Array,  # [C, N] f32
    mask: jax.Array,  # [1, N] or [N] — nonzero selects the row
    cap: int,
) -> tuple[jax.Array, jax.Array]:
    """Compaction oracle: (out [C, cap] with the first min(count, cap)
    qualifying rows then zeros, total count).  Matches engine.ops.compact's
    nonzero+gather semantics."""
    mask = mask.reshape(-1) != 0
    n = mask.shape[0]
    idx = jnp.nonzero(mask, size=cap, fill_value=n)[0]
    in_range = idx < n
    safe = jnp.where(in_range, idx, 0)
    out = jnp.take(cols, safe, axis=1)
    out = jnp.where(in_range[None, :], out, 0.0)
    return out, jnp.sum(mask.astype(jnp.int32))
