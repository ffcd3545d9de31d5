"""Fused block compaction kernel (the pushdown "return qualifying rows" path).

``engine.ops.compact`` materializes qualifying rows with ``jnp.nonzero`` +
gather: one full pass to build the index vector in HBM, then one gather pass
per column.  The fused plan is one pass: each input block computes its mask
count and in-block prefix offsets (exclusive prefix sum of the mask), packs
its qualifying rows with a permutation matmul, and writes them densely into
a capacity-bounded output buffer at the running global offset.

Mechanics per SUB-row sub-tile (SUB = 512):

  * ``pos`` = exclusive prefix sum of the mask (log-step lane rotations) —
    each qualifying row's slot among the sub-tile's qualifiers;
  * position-free compaction: ``P[j, r] = mask[r] & (pos[r] == j)`` is a
    ``[SUB, SUB]`` 0/1 matrix whatever the running offset, and
    ``cols_sub [C, SUB] @ P^T`` packs the qualifiers into slots
    ``[0, cnt)`` of a ``[C, SUB]`` tile, zeros beyond (MXU work instead of
    an unsupported vector scatter);
  * one exact bf16 pass: each f32 value is split by truncation into three
    bf16 planes (``hi``, ``mid``, ``lo``) that share no bits, the planes
    are stacked as ``[3C, SUB]`` rows and multiplied by ``P`` in bf16 with
    f32 accumulation once, and ``(hi + mid) + lo`` rebuilds every value bit
    for bit.  Each slot receives one product ``1.0 * plane`` and zeros, so
    nothing is rounded; the range this holds for is :func:`block_compact`'s;
  * placement by a lane rotation: ``pltpu.roll`` by the running offset's
    position within its tile moves the packed rows to their lanes, and
    selects against the lanes already filled merge them — every value is
    copied, never added.

Each loop iteration packs :data:`_PER_STEP` sub-tiles, then places them in
order; the streaming kernel emits the tiles they complete after the last
placement, so no branch splits the group.  The packs do not depend on the
running offset, so their matmuls overlap one another and the placements'
scalar chain; only the count, the carry tile and the tile index pass from
one sub-tile to the next.

Capacity semantics match the ``nonzero(size=cap)`` oracle: qualifying rows
with global position >= cap are dropped, slots in [count, cap) are zero.
The returned count is exact and independent of ``cap``.

Two variants share that per-sub-tile compaction core:

  * the **resident** kernel keeps the whole padded ``[C, cap]`` output in
    VMEM, so ``cap`` is bounded by the ~8 MB VMEM budget — fine for the
    low-selectivity points, impossible for the 6M-row sweep at high
    selectivity.  A sub-tile lands at ``base``, the global running count,
    clamped into a pad region once it passes ``cap``.  Stores start on a
    lane tile: the packed tile is rotated by ``skew = base % 128`` and
    stored as ``[C, SUB]`` at the tile holding ``base``, keeping the rows
    already written below ``base`` in that tile, plus a ``[C, 128]`` tail
    that takes the rows rotated round (zeros beyond).  Slots past the
    sub-tile's own count hold zeros and are overwritten by the next
    sub-tile's store (TPU grids iterate sequentially, so later stores win).
    The count rides in an i32 [1, LANES] tile that doubles as the
    running-offset carry between grid steps.
  * the **streaming** kernel (:func:`block_compact_stream`) keeps the output
    in HBM (``pl.ANY``) and emits each completed SUB-wide tile with a
    double-buffered manual DMA (:mod:`repro.kernels.pipeline`), overlapping
    the copy of tile *i* with the compute of the sub-tiles that fill tile
    *i+1*.  Capacity is HBM-bounded.

The streaming write path cannot reuse the resident kernel's overlapping-
store trick: two in-flight DMAs to overlapping HBM ranges have no ordering,
so stores must be exact-length and disjoint.  Instead a one-sub-tile carry
buffer holds the partially-filled tail tile (``fill`` rows).  The packed
sub-tile is rotated by ``fill``: its lanes ``>= fill`` complete the carry
tile and its lanes ``< fill`` (the rows rotated round) start the next one.
Whenever the carry fills a whole tile it is emitted at a SUB-aligned HBM
offset (aligned + disjoint = safe to double-buffer).  The final partial
tile is flushed by the epilogue in :func:`stream_finalize`.

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
SUB = 512  # sub-tile width: the compaction permutation is [SUB, SUB] bf16
_PER_STEP = 8  # sub-tiles a loop iteration (fastest of 1, 2, 4 and 8 on a v5e)
_WINDOW = SUB + LANES  # resident store window: one sub-tile plus the lane skew
_HI16 = -(1 << 16)  # 0xFFFF0000: an f32's sign, exponent and top 7 mantissa bits


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


def _trunc16(x: jax.Array) -> jax.Array:
    """``x`` with the low 16 bits cleared: a bf16 value, truncated toward 0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jax.lax.bitcast_convert_type(bits & _HI16, jnp.float32)


def _bf16_planes(x: jax.Array) -> jax.Array:
    """Split f32 ``[C, n]`` into ``[3C, n]`` bf16 planes with ``(hi + mid) + lo == x``.

    Truncation, not rounding, so no plane overflows to inf; ``x - hi`` and
    ``rest - mid`` are exact, and ``lo`` has at most 8 significant bits, so
    it is a bf16 while it is normal (``|x| >= 2^-103``).
    """
    hi = _trunc16(x)
    rest = x - hi
    mid = _trunc16(rest)
    return jnp.concatenate([hi, mid, rest - mid], axis=0).astype(jnp.bfloat16)


def _pack(sub: jax.Array, m: jax.Array) -> jax.Array:
    """Pack the qualifying rows of one sub-tile into slots ``[0, cnt)`` of ``[C, SUB]``.

    Qualifying row ``r`` lands at slot ``(exclusive prefix of m)[r]``; every
    other slot is zero.  One bf16 matmul against the 0/1 matrix ``P[j, r]``
    over the three planes of :func:`_bf16_planes`, exact (module docstring).
    """
    c = sub.shape[0]
    target = jnp.where(m != 0, _exclusive_prefix(m), -1)  # [1, SUB]
    slots = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    perm = (slots == target).astype(jnp.bfloat16)
    planes = jax.lax.dot_general(
        _bf16_planes(sub), perm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (planes[:c] + planes[c:2 * c]) + planes[2 * c:]


def _pack_sub_tile(cols_ref, mask_ref, s) -> tuple[jax.Array, jax.Array]:
    """Sub-tile ``s`` of the block packed (:func:`_pack`), and its count."""
    m = mask_ref[:, pl.ds(s * SUB, SUB)]  # [1, SUB] i32
    return _pack(cols_ref[:, pl.ds(s * SUB, SUB)], m), jnp.sum(m)


def _over_sub_tiles(n: int, group, carry):
    """``carry = group(subs, carry)`` over the sub-tiles ``0 .. n-1``.

    ``subs`` lists :data:`_PER_STEP` sub-tile indices a ``fori_loop``
    iteration (Pallas TPU takes only ``unroll=1`` or a full unroll, so a
    group is a Python loop), then the ``n % _PER_STEP`` left over.  A group
    packs all its sub-tiles before it places any, so their matmuls overlap
    one another and the placements' scalar chain.
    """
    def body(i, carry):
        return group([i * _PER_STEP + k for k in range(_PER_STEP)], carry)

    carry = jax.lax.fori_loop(0, n // _PER_STEP, body, carry)
    if n % _PER_STEP:
        carry = group(list(range(n - n % _PER_STEP, n)), carry)
    return carry


def _kernel(cols_ref, mask_ref, out_ref, cnt_ref, *, cap: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    bn = cols_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, SUB), 1)

    def group(subs, base):
        for packed, cnt in [_pack_sub_tile(cols_ref, mask_ref, s) for s in subs]:
            # Rows past cap are dropped: clamp the store into the pad
            # region, where it only ever overwrites other dropped rows.
            start = jnp.minimum(base, cap)
            # Stores start on a lane tile: rotate the packed rows by the
            # skew, keep the rows already written below ``start`` in its
            # tile, and put the rows rotated round into the next tile.
            skew = jax.lax.rem(start, LANES)
            at = pl.multiple_of(start - skew, LANES)
            rot = pltpu.roll(packed, skew, 1)
            head = pl.ds(at, SUB)
            out_ref[:, head] = jnp.where(lane < skew, out_ref[:, head], rot)
            out_ref[:, pl.ds(pl.multiple_of(at + SUB, LANES), LANES)] = jnp.where(
                lane[:, :LANES] < skew, rot[:, :LANES], 0.0
            )
            base = base + cnt
        return base

    total = _over_sub_tiles(bn // SUB, group, cnt_ref[0, 0])
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

    Rows are copied bit for bit where every value is +0 or finite with
    ``2^-103 <= |x| < 2^127``.  ``-0.0`` comes back as ``+0.0``, a NaN or
    inf anywhere in a sub-tile poisons its slots, and below ``2^-103`` the
    lowest bf16 plane is subnormal and may be flushed.
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
# carry [C, SUB] f32).  State lanes: 0 = total mask count so far, 1 = next
# tile index (the carry tile's global offset is tile * SUB, and it holds
# total - tile * SUB rows).

_TOTAL, _TILE = 0, 1


def _pack_state(total, tile):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return jnp.where(lane == _TOTAL, total, jnp.where(lane == _TILE, tile, 0))


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
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, SUB), 1)

    def group(subs, st):
        total, tile, seq, carry = st
        emits = []
        for packed, cnt in [_pack_sub_tile(cols_ref, mask_ref, s) for s in subs]:
            # The carry holds ``fill`` rows; rotated by ``fill``, the packed
            # rows complete it from lane ``fill`` on, and those rotated
            # round to lanes [0, fill) start the next tile.
            fill = total - tile * SUB
            rot = pltpu.roll(packed, fill, 1)
            merged = jnp.where(lane >= fill, rot, carry)
            is_full = fill + cnt >= SUB
            emits.append((is_full & (tile < pad_tile), tile, merged))
            carry = jnp.where(is_full, jnp.where(lane < fill, rot, 0.0), merged)
            tile = tile + is_full.astype(jnp.int32)
            total = total + cnt
        # The group's completed tiles go out after its placements, so no
        # branch splits the placements from one another.
        for emit_now, at, merged in emits:

            @pl.when(emit_now)
            def _emit():
                pipeline.emit_tile(
                    stage_ref, sem_ref, seq, merged,
                    out_ref.at[:, pl.ds(at * SUB, SUB)],
                )

            seq = seq + emit_now.astype(jnp.int32)
        return total, tile, seq, carry

    total, tile, seq, carry = _over_sub_tiles(
        bn // SUB, group,
        (state_ref[0, _TOTAL], state_ref[0, _TILE], jnp.int32(0), carry_ref[...]),
    )
    # Settle this grid step's in-flight copies: scratch DMA semaphores must
    # be zero when the kernel ends, and the input pipeline may rotate our
    # staging source underneath an unfinished copy otherwise.
    pipeline.drain(stage_ref, sem_ref, seq, out_ref.at[:, pl.ds(0, SUB)])
    carry_ref[...] = carry
    state_ref[...] = _pack_state(total, tile)


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


@functools.partial(jax.jit, static_argnames=("cap", "block_n", "interpret"))
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
    Jitted, so a driver that calls it for many chunks of one shape traces
    and lowers the kernel once, not once a chunk.
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
