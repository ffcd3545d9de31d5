"""Fused block compaction kernel (the pushdown "return qualifying rows" path).

``engine.ops.compact`` materializes qualifying rows with ``jnp.nonzero`` +
gather: one full pass to build the index vector in HBM, then one gather pass
per column.  The fused plan is one pass: each input block computes its mask
count and in-block prefix offsets (exclusive prefix sum of the mask), converts
the offsets into a scatter permutation, and writes its qualifying rows
densely into a capacity-bounded output buffer at the running global offset.

Mechanics per SUB-row sub-tile (SUB = 512, keeps the permutation matrix at
SUB x SUB f32 = 1 MB):

  * ``pos`` = exclusive prefix sum of the mask (log-step lane rotations) —
    each qualifying row's slot among the sub-tile's qualifiers;
  * scatter-as-matmul: ``P[j, r] = mask[r] & (pos[r] == j)``, and
    ``cols_sub [C, SUB] @ P^T`` lands every qualifying row at its slot (MXU
    work at full f32 precision instead of an unsupported vector scatter);
  * the compacted sub-tile lands at ``out[:, base : base + SUB]`` where
    ``base`` is the global running count — slots past the sub-tile's own
    count hold zeros and are overwritten by the next sub-tile's store (TPU
    grids iterate sequentially, so later stores win).  Stores start on a
    lane tile: the scatter targets a ``[C, SUB + LANES]`` window that
    begins at the tile holding ``base``, and the rows already written below
    ``base`` in that tile are kept.

Capacity semantics match the ``nonzero(size=cap)`` oracle: qualifying rows
with global position >= cap are dropped, slots in [count, cap) are zero.
The output buffer is padded by one store window so an almost-full store
never writes out of bounds (stores whose base would pass ``cap`` clamp into
the trimmed pad region).

The returned count is exact and independent of ``cap``; it rides in an i32
[1, LANES] tile that doubles as the running-offset carry between grid steps.

Two variants share that per-sub-tile compaction core:

  * the **resident** kernel above keeps the whole padded ``[C, cap]`` output
    in VMEM, so ``cap`` is bounded by the ~8 MB VMEM budget — fine for the
    low-selectivity points, impossible for the 6M-row sweep at high
    selectivity;
  * the **streaming** kernel (:func:`block_compact_stream`) keeps the output
    in HBM (``pl.ANY``) and emits each completed SUB-wide tile with a
    double-buffered manual DMA (:mod:`repro.kernels.pipeline`), overlapping
    the copy of tile *i* with the mask/prefix/scatter-matmul compute of the
    sub-tiles that fill tile *i+1*.  Capacity is HBM-bounded.

The streaming write path cannot reuse the resident kernel's overlapping-
store trick: two in-flight DMAs to overlapping HBM ranges have no ordering,
so stores must be exact-length and disjoint.  Instead a one-sub-tile carry
buffer holds the partially-filled tail tile; each sub-tile's qualifying rows
are scattered directly to ``carry_fill + pos`` slots of a ``[C, 2*SUB]``
window (one widened scatter matmul), the first half merges with the carry,
and whenever the carry fills a whole tile it is emitted at a SUB-aligned
HBM offset (aligned + disjoint = safe to double-buffer).  The final
partial tile is flushed by the epilogue in :func:`stream_finalize`.

Overflow keeps oracle semantics without per-row drops: a tile whose base
passes ``cap`` is simply not emitted (every row in it has global position
>= cap), and the tile straddling ``cap`` lands in the trimmed ``[cap,
cap_ceil)`` pad region.  Chunking: the kernel threads (out, state, carry)
through ``input_output_aliases``, so a driver may split an arbitrarily long
input across calls — ``stream_init`` / ``stream_chunk`` / ``stream_finalize``
are the composable surface the chunked driver in :mod:`repro.kernels.ops`
uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pipeline

LANES = 128
SUB = 512  # sub-tile width: the scatter permutation is [SUB, SUB] f32
_WINDOW = SUB + LANES  # resident store window: one sub-tile plus the lane skew


def _exclusive_prefix(m: jax.Array) -> jax.Array:
    """Exclusive prefix sum along the lanes of a ``[1, SUB]`` i32 row.

    Log-step shifted adds (Hillis-Steele) on lane rotations: Mosaic lowers
    ``pltpu.roll``, not ``jnp.cumsum``.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    x = m
    shift = 1
    while shift < m.shape[1]:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0)
        shift *= 2
    return x - m


def _scatter(sub: jax.Array, m: jax.Array, fill, width: int) -> jax.Array:
    """Pack the qualifying rows of one sub-tile into ``[C, width]`` slots.

    Qualifying row ``r`` lands at slot ``fill + (exclusive prefix of m)[r]``;
    every other slot is zero.  The scatter is a matmul against the 0/1
    matrix ``P[j, r]`` (MXU work instead of an unsupported vector scatter),
    at full f32 precision so every value is copied exactly.
    """
    target = jnp.where(m != 0, _exclusive_prefix(m) + fill, -1)  # [1, SUB]
    slots = jax.lax.broadcasted_iota(jnp.int32, (width, SUB), 0)
    perm = (slots == target).astype(jnp.float32)
    return jax.lax.dot_general(
        sub, perm, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _kernel(cols_ref, mask_ref, out_ref, cnt_ref, *, cap: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    bn = cols_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _WINDOW), 1)

    def body(s, base):
        m = mask_ref[:, pl.ds(s * SUB, SUB)]  # [1, SUB] i32
        sub = cols_ref[:, pl.ds(s * SUB, SUB)]  # [C, SUB]
        # Rows past cap are dropped: clamp the store into the pad region,
        # where it only ever overwrites other dropped rows.
        start = jnp.minimum(base, cap)
        # Stores must start on a lane tile: scatter into a window that
        # begins at the tile holding ``start`` and keep the rows already
        # written below ``start`` in that tile.
        skew = jax.lax.rem(start, LANES)
        at = pl.ds(pl.multiple_of(start - skew, LANES), _WINDOW)
        window = _scatter(sub, m, skew, _WINDOW)
        out_ref[:, at] = jnp.where(lane < skew, out_ref[:, at], window)
        return base + jnp.sum(m)

    base0 = cnt_ref[0, 0]
    total = jax.lax.fori_loop(0, bn // SUB, body, base0)
    cnt_ref[...] = jnp.full((1, LANES), total, jnp.int32)


def _resident_width(cap: int) -> int:
    """Columns of the resident output: the last store window starts on the
    lane tile holding ``cap`` and must stay in bounds."""
    return cap - cap % LANES + _WINDOW


def resident_bytes(c: int, cap: int) -> int:
    """VMEM the resident kernel's ``[c, cap]`` output occupies, padding included."""
    return c * _resident_width(cap) * 4


def block_compact(
    cols: jax.Array,  # [C, N] f32 column block
    mask: jax.Array,  # [1, N] i32 (0/1) row mask
    cap: int,
    *,
    block_n: int = 65536,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out [C, cap] f32, count i32 scalar).

    ``out[:, j]`` is the j-th qualifying row for ``j < min(count, cap)``,
    zero beyond; ``count`` is the total mask population regardless of cap.
    """
    c, n = cols.shape
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    assert bn % SUB == 0, (bn, SUB)
    assert cap >= 1

    width = _resident_width(cap)
    out, cnt = pl.pallas_call(
        functools.partial(_kernel, cap=cap),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((c, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=(
            pl.BlockSpec((c, width), lambda i: (0, 0)),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((c, width), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="block_compact",
    )(cols, mask)
    return out[:, :cap], cnt[0, 0]


# ---------------------------------------------------------------------------
# Streaming variant: HBM-resident output, double-buffered DMA emission.
#
# Cross-chunk state is (out [C, cap_ceil + SUB] in HBM, state [1, LANES] i32,
# carry [C, SUB] f32).  State lanes: 0 = total mask count so far, 1 = carry
# fill (rows held in the carry tile), 2 = next tile index (global offset of
# the carry tile is tile * SUB).

_TOTAL, _FILL, _TILE = 0, 1, 2


def _pack_state(total, fill, tile):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    st = jnp.where(lane == _TOTAL, total, 0)
    st = jnp.where(lane == _FILL, fill, st)
    return jnp.where(lane == _TILE, tile, st)


def _stream_kernel(
    cols_ref, mask_ref, state_in_ref, carry_in_ref, hbm_in_ref,
    out_ref, state_ref, carry_ref,
    stage_ref, sem_ref, *, cap_ceil: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():  # fold the previous chunk's state into the revisited tiles
        state_ref[...] = state_in_ref[...]
        carry_ref[...] = carry_in_ref[...]

    bn = cols_ref.shape[1]
    pad_tile = cap_ceil // SUB  # first tile index wholly past cap: not emitted

    def body(s, st):
        total, fill, tile, seq, carry = st
        m = mask_ref[:, pl.ds(s * SUB, SUB)]  # [1, SUB] i32
        sub = cols_ref[:, pl.ds(s * SUB, SUB)]  # [C, SUB]
        # Slot among (carry rows + this sub-tile's qualifiers): the widened
        # scatter lands row r at fill + (exclusive prefix of mask)[r], so
        # the carry merge is a plain add against disjoint zero slots.
        cnt = jnp.sum(m)
        window = _scatter(sub, m, fill, 2 * SUB)  # qualifiers at [fill, fill + cnt)
        merged = carry + window[:, :SUB]
        spill = window[:, SUB:]
        new_fill = fill + cnt
        is_full = new_fill >= SUB
        emit_now = is_full & (tile < pad_tile)

        @pl.when(emit_now)
        def _emit():
            pipeline.emit_tile(
                stage_ref, sem_ref, seq, merged,
                out_ref.at[:, pl.ds(tile * SUB, SUB)],
            )

        carry = jnp.where(is_full, spill, merged)
        fill = jnp.where(is_full, new_fill - SUB, new_fill)
        tile = tile + is_full.astype(jnp.int32)
        seq = seq + emit_now.astype(jnp.int32)
        return total + cnt, fill, tile, seq, carry

    total, fill, tile, seq, carry = jax.lax.fori_loop(
        0, bn // SUB, body,
        (state_ref[0, _TOTAL], state_ref[0, _FILL], state_ref[0, _TILE],
         jnp.int32(0), carry_ref[...]),
    )
    # Settle this grid step's in-flight copies: scratch DMA semaphores must
    # be zero when the kernel ends, and the input pipeline may rotate our
    # staging source underneath an unfinished copy otherwise.
    pipeline.drain(stage_ref, sem_ref, seq, out_ref.at[:, pl.ds(0, SUB)])
    carry_ref[...] = carry
    state_ref[...] = _pack_state(total, fill, tile)


def _cap_ceil(cap: int) -> int:
    return -(-cap // SUB) * SUB


def stream_init(c: int, cap: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fresh (out, state, carry) streaming state for a [c, N] compaction.

    ``out`` is the HBM-resident output, one SUB-tile wider than
    ``cap_ceil`` so the tile straddling ``cap`` always has somewhere exact
    to land; the zeros-init is one write pass that gives ``[count, cap)``
    its oracle zeros without any in-kernel zero-fill traffic.
    """
    return (
        jnp.zeros((c, _cap_ceil(cap) + SUB), jnp.float32),
        jnp.zeros((1, LANES), jnp.int32),
        jnp.zeros((c, SUB), jnp.float32),
    )


def stream_chunk(
    state: tuple[jax.Array, jax.Array, jax.Array],
    cols: jax.Array,  # [C, n] f32, n a multiple of SUB
    mask: jax.Array,  # [1, n] i32 (0/1)
    cap: int,
    *,
    block_n: int = 65536,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compact one input chunk into the running stream state.

    The HBM output buffer is threaded through ``input_output_aliases`` so
    successive chunks DMA into ONE allocation — no copy of the (possibly
    many-MB) output per call; offset and count carry in the state tile.
    """
    out, st, carry = state
    c, n = cols.shape
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    assert bn % SUB == 0, (bn, SUB)
    assert cap >= 1
    cap_pad = _cap_ceil(cap) + SUB
    assert out.shape == (c, cap_pad), (out.shape, c, cap_pad)

    out, st, carry = pl.pallas_call(
        functools.partial(_stream_kernel, cap_ceil=_cap_ceil(cap)),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((c, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
            pl.BlockSpec((c, SUB), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
            pl.BlockSpec((c, SUB), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((c, cap_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((c, SUB), jnp.float32),
        ),
        scratch_shapes=list(pipeline.emit_slots(c, SUB, jnp.float32)),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="block_compact",
    )(cols, mask, st, carry, out)
    return out, st, carry


def stream_finalize(
    state: tuple[jax.Array, jax.Array, jax.Array], cap: int
) -> tuple[jax.Array, jax.Array]:
    """Epilogue: flush the ragged carry tail, trim to cap, return count.

    The carry tile holds ``fill < SUB`` rows (zeros beyond), written as one
    exact-length update at the running offset — clamped into the pad tile
    when the stream already passed ``cap``, where it only covers dropped
    rows.
    """
    out, st, carry = state
    start = jnp.minimum(st[0, _TILE] * SUB, _cap_ceil(cap))
    out = jax.lax.dynamic_update_slice(out, carry, (0, start))
    return out[:, :cap], st[0, _TOTAL]


def block_compact_stream(
    cols: jax.Array,  # [C, N] f32 column block
    mask: jax.Array,  # [1, N] i32 (0/1) row mask
    cap: int,
    *,
    block_n: int = 65536,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Single-call streaming compaction; same contract as :func:`block_compact`
    with ``cap`` bounded by HBM instead of VMEM."""
    state = stream_init(cols.shape[0], cap)
    state = stream_chunk(
        state, cols, mask, cap, block_n=block_n, interpret=interpret
    )
    return stream_finalize(state, cap)
