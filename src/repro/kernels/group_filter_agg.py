"""Single-pass fused grouped filter+aggregate kernel (the DBMS hot loop).

The DBMS workloads (paper §3.6, Fig. 15) and the pushdown plans filter and
aggregate in many shapes: Q6 and the pushdown aggregate are one group
behind range predicates, Q1 is a 6-group × 5-aggregate scan with two
derived columns, Q12 is grouped conditional counts behind four predicates.
Their unfused jnp graphs stream every column through HBM several times
(mask pass, derived-column passes, then one ``segment_sum`` pass per
aggregate).

This kernel makes any such query ONE pass over a ``[C, N]`` column block:

  * a small **predicate program** arrives in SMEM — K predicates, each
    either a range test ``lo <= cols[a] < hi`` or a column compare
    ``cols[a] < cols[b]``, AND-combined into the row mask in registers;
  * an **aggregate program** (also SMEM) — A aggregates, each the product
    of up to 3 *terms*, where a term transforms one column
    (identity / ``1-c`` / ``1+c`` / ``c <= const`` / ``c > const``).  Q1's
    derived ``disc_price = price * (1 - discount)`` and
    ``charge = disc_price * (1 + tax)`` are term products evaluated
    in-register, never materialized in HBM;
  * per-group accumulation for G dictionary-coded groups goes through the
    MXU: a one-hot group membership times the masked per-row values
    (``onehot[G, bn] @ vals[bn, A+1]``) at full f32 precision; TPU grids
    iterate sequentially, so the running accumulator across blocks, a
    VMEM-resident output, is safe;
  * the constants of both programs carry a leading program dimension B, so
    one pass over the columns serves B requests of the same query shape
    (scan sharing); a single query is ``B = 1``.  A grid step runs
    ``P = programs_per_pass(B, A)`` programs over its block in ONE dot:
    each predicate's columns and each term's column transform are read
    once, each program applies its own constants, and the programs' masked
    values ``[P * (A + 1), bn]``, stacked, meet one shared one-hot
    ``[Gp, bn]`` (G padded to 8).  P is as large as keeps the stack within
    one 128-row MXU tile: the served queries at ``B <= 8`` take one pass,
    ``B = 1`` one program a step.

Padding contract: rows whose key is outside ``[0, num_groups)`` (the ops
wrapper pads with -1) match no one-hot row and therefore contribute to no
group, regardless of what the predicate program evaluates to on padded
junk — padding correctness does not depend on the program.

Output layout of :func:`group_filter_agg`: ``out[b, g, a]`` = sum of
aggregate ``a`` over the rows of group ``g`` that program ``b`` selects,
for ``a < A``; ``out[b, g, A]`` = that masked row count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
MXU_TILE = 128  # value rows one one-hot dot may take

# Predicate opcodes (pred_ops[k, 0]).
PRED_RANGE = 0  # lo <= cols[a] < hi
PRED_LT = 1  # cols[a] < cols[b]

# Aggregate term modes (agg_ops[k, 2*t]).
TERM_NONE = 0  # 1.0 (unused term slot)
TERM_COL = 1  # cols[i]
TERM_ONE_MINUS = 2  # 1 - cols[i]
TERM_ONE_PLUS = 3  # 1 + cols[i]
TERM_LE = 4  # cols[i] <= const  (0/1 indicator)
TERM_GT = 5  # cols[i] > const   (0/1 indicator)

MAX_TERMS = 3

_FLOAT_MIN = float(np.finfo(np.float32).min)
_FLOAT_MAX = float(np.finfo(np.float32).max)


# ---------------------------------------------------------------------------
# Program encoding: tiny int/float tables, built on the host.  They are numpy
# arrays: a caller places them on the device (or hands them to ``jit``, which
# makes them trace-time constants).
def encode_predicates(preds) -> tuple[np.ndarray, np.ndarray]:
    """preds: sequence of ("range", col, lo, hi) | ("lt", col_a, col_b).

    ``lo``/``hi`` may be ``None`` for an open bound.  Returns
    (pred_ops [K, 3] i32, pred_consts [K, 2] f32); K >= 1 (an empty program
    encodes one always-true range predicate on column 0).
    """
    ops, consts = [], []
    for p in preds:
        kind = p[0]
        if kind == "range":
            _, col, lo, hi = p
            ops.append((PRED_RANGE, int(col), 0))
            consts.append((
                _FLOAT_MIN if lo is None else float(lo),
                _FLOAT_MAX if hi is None else float(hi),
            ))
        elif kind == "lt":
            _, a, b = p
            ops.append((PRED_LT, int(a), int(b)))
            consts.append((0.0, 0.0))
        else:
            raise ValueError(f"unknown predicate kind {kind!r}")
    if not ops:
        ops.append((PRED_RANGE, 0, 0))
        consts.append((_FLOAT_MIN, _FLOAT_MAX))
    return np.asarray(ops, np.int32), np.asarray(consts, np.float32)


_TERM_CODES = {
    "col": TERM_COL,
    "one_minus": TERM_ONE_MINUS,
    "one_plus": TERM_ONE_PLUS,
    "le": TERM_LE,
    "gt": TERM_GT,
}


def encode_aggregates(aggs) -> tuple[np.ndarray, np.ndarray]:
    """aggs: sequence of aggregates; each is a sequence of <= MAX_TERMS terms.

    A term is ("col", i) | ("one_minus", i) | ("one_plus", i)
    | ("le", i, const) | ("gt", i, const).  The aggregate's per-row value is
    the product of its terms.  Returns (agg_ops [A, 2*MAX_TERMS] i32,
    agg_consts [A, MAX_TERMS] f32).
    """
    if not aggs:
        raise ValueError("need at least one aggregate")
    ops = np.zeros((len(aggs), 2 * MAX_TERMS), np.int32)
    consts = np.zeros((len(aggs), MAX_TERMS), np.float32)
    for a, terms in enumerate(aggs):
        if not 1 <= len(terms) <= MAX_TERMS:
            raise ValueError(f"aggregate {a}: need 1..{MAX_TERMS} terms, got {len(terms)}")
        for t, term in enumerate(terms):
            kind = _TERM_CODES.get(term[0])
            if kind is None:
                raise ValueError(f"unknown term kind {term[0]!r}")
            ops[a, 2 * t] = kind
            ops[a, 2 * t + 1] = int(term[1])
            if kind in (TERM_LE, TERM_GT):
                consts[a, t] = float(term[2])
    return ops, consts


# ---------------------------------------------------------------------------
def programs_per_pass(num_progs: int, num_aggs: int) -> int:
    """Programs one grid step folds into a single one-hot dot: as many as
    keep their stacked value rows ``P * (A + 1)`` within one MXU tile."""
    return min(num_progs, MXU_TILE // (num_aggs + 1))


def _eval_masks(pred_ops_ref, pred_consts_ref, cols_ref, num_preds: int, progs):
    """Row masks [1, bn] as f32 0/1, one per program in ``progs``, from its
    predicates (ANDed).  Each predicate's columns and column compare are
    read once for all of them.

    Each predicate's two candidate outcomes are converted to f32 before the
    opcode select: Mosaic cannot select between boolean vectors.
    """
    masks = [None] * len(progs)
    for k in range(num_preds):
        kind = pred_ops_ref[k, 0]
        ca = cols_ref[pl.ds(pred_ops_ref[k, 1], 1), :]
        cb = cols_ref[pl.ds(pred_ops_ref[k, 2], 1), :]
        less = (ca < cb).astype(jnp.float32)
        for j, prog in enumerate(progs):
            lo = pred_consts_ref[prog, k, 0]
            hi = pred_consts_ref[prog, k, 1]
            in_range = ((ca >= lo) & (ca < hi)).astype(jnp.float32)
            m = jnp.where(kind == PRED_RANGE, in_range, less)
            masks[j] = m if masks[j] is None else masks[j] * m
    return masks


def _eval_values(agg_ops_ref, agg_consts_ref, cols_ref, a: int, progs):
    """Per-row values [1, bn] of aggregate ``a``, one per program in
    ``progs``: the product of its terms.  Each term's column transform is
    built once for all of them; its ``le``/``gt`` indicator comes from each
    program's constants."""
    vals = [None] * len(progs)
    for t in range(MAX_TERMS):
        mode = agg_ops_ref[a, 2 * t]
        c = cols_ref[pl.ds(agg_ops_ref[a, 2 * t + 1], 1), :].astype(jnp.float32)
        term = jnp.where(mode == TERM_COL, c, 1.0)
        term = jnp.where(mode == TERM_ONE_MINUS, 1.0 - c, term)
        term = jnp.where(mode == TERM_ONE_PLUS, 1.0 + c, term)
        for j, prog in enumerate(progs):
            const = agg_consts_ref[prog, a, t]
            tj = jnp.where(mode == TERM_LE, (c <= const).astype(jnp.float32), term)
            tj = jnp.where(mode == TERM_GT, (c > const).astype(jnp.float32), tj)
            vals[j] = tj if vals[j] is None else vals[j] * tj
    return vals


def _kernel(
    pred_ops_ref,
    pred_consts_ref,  # [B, K, 2] SMEM: per-program predicate constants
    agg_ops_ref,
    agg_consts_ref,  # [B, A, MAX_TERMS] SMEM: per-program term constants
    cols_ref,
    keys_ref,
    out_ref,  # the whole [passes, Gp, LANES] output, resident for the entire grid
    *,
    num_preds: int,
    num_aggs: int,
    num_progs: int,
    per_pass: int,
):
    i = pl.program_id(0)  # data block (outer grid dim)
    p = pl.program_id(1)  # pass (inner grid dim)

    @pl.when(i == 0)
    def _init():
        out_ref[p] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    # The pass's programs; a short last pass repeats the last one.
    progs = [jnp.minimum(p * per_pass + j, num_progs - 1) for j in range(per_pass)]
    masks = _eval_masks(pred_ops_ref, pred_consts_ref, cols_ref, num_preds, progs)

    # Per program, its masked aggregate values and then its mask, which the
    # dot turns into the masked count: [A + 1, bn] a program, stacked.
    rows = [[] for _ in progs]
    for a in range(num_aggs):
        vals = _eval_values(agg_ops_ref, agg_consts_ref, cols_ref, a, progs)
        for r, v, m in zip(rows, vals, masks):
            r.append(v * m)
    for r, m in zip(rows, masks):
        r.append(m)
    stacked = jnp.concatenate([v for r in rows for v in r], axis=0)

    # One-hot group membership [Gp, bn] (G padded to 8), shared by every
    # program; padded rows carry key -1 and match no row of the iota, so
    # they vanish from every group.
    keys = keys_ref[...]  # [1, bn] i32
    group_ids = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[1], keys.shape[1]), 0)
    onehot = (group_ids == keys).astype(jnp.float32)

    # [Gp, bn] x [P (A+1), bn]^T -> [Gp, P (A+1)]: the grouped aggregation
    # of every program of the pass in one MXU pass at full f32 precision;
    # program j's sums and count are columns [j (A+1), (j+1) (A+1)).
    upd = jax.lax.dot_general(
        onehot, stacked, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    out_ref[p] += jnp.pad(upd, ((0, 0), (0, LANES - upd.shape[1])))


def group_filter_agg(
    cols: jax.Array,  # [C, N] f32 column block, scanned once for all programs
    keys: jax.Array,  # [1, N] i32 dictionary-coded group ids (may be -1 = pad)
    pred_ops: jax.Array,  # [K, 3] i32 predicate program, shared across the batch
    pred_consts: jax.Array,  # [B, K, 2] f32 per-program predicate constants
    agg_ops: jax.Array,  # [A, 2*MAX_TERMS] i32 aggregate program, shared
    agg_consts: jax.Array,  # [B, A, MAX_TERMS] f32 per-program term constants
    *,
    num_groups: int,
    block_n: int = 16384,
    interpret: bool = False,
) -> jax.Array:
    """B programs of one query shape over one HBM pass of the columns.

    All programs share one opcode structure but carry their own constants,
    so N concurrent q6 requests with different predicate bounds become one
    kernel invocation (scan sharing); a single query is the ``B = 1`` case.
    The grid is ``(blocks, passes)``.  Each grid step runs
    ``P = programs_per_pass(B, A)`` programs over the ``[C, bn]`` column
    block: each program's mask and masked values, stacked, meet one one-hot
    group membership in one dot.  The served queries at ``B <= 8`` take one
    pass; ``B = 1`` is one program a step.  A batch wider than P takes
    several passes, whose column block stays in VMEM across the inner grid
    dimension.  The ``[passes, Gp, LANES]`` output has a constant index
    map, so it stays resident in VMEM for the whole grid and is written
    back once at the end; each program accumulates into its own columns of
    it, visiting the data blocks in order, as the ``B = 1`` call does.

    Returns ``[B, num_groups, A + 1]`` f32: per-group aggregate sums, then
    the masked count.
    """
    _, n = cols.shape
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    num_progs = pred_consts.shape[0]
    assert agg_consts.shape[0] == num_progs, (pred_consts.shape, agg_consts.shape)
    num_preds = pred_ops.shape[0]
    num_aggs = agg_ops.shape[0]
    assert num_aggs + 1 <= LANES, num_aggs
    assert num_groups >= 1
    per_pass = programs_per_pass(num_progs, num_aggs)
    passes = -(-num_progs // per_pass)
    rows = -(-num_groups // SUBLANES) * SUBLANES

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            num_preds=num_preds,
            num_aggs=num_aggs,
            num_progs=num_progs,
            per_pass=per_pass,
        ),
        grid=(n // bn, passes),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((cols.shape[0], bn), lambda i, p: (0, i)),
            pl.BlockSpec((1, bn), lambda i, p: (0, i)),
        ],
        out_specs=pl.BlockSpec((passes, rows, LANES), lambda i, p: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((passes, rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="group_filter_agg",
    )(pred_ops, pred_consts, agg_ops, agg_consts, cols, keys)
    width = num_aggs + 1
    out = out[:, :num_groups, : per_pass * width].reshape(passes, num_groups, per_pass, width)
    return out.transpose(0, 2, 1, 3).reshape(passes * per_pass, num_groups, width)[:num_progs]
