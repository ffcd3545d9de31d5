"""Double-buffered VMEM->HBM DMA emit pipeline (manual async copies).

Pallas pipelines *inputs* for free (BlockSpec index maps), but kernels whose
output lives in HBM (``pl.ANY`` memory space) must move every result tile
themselves.  The naive way — compute a tile, DMA it, wait, compute the next —
serializes the store path behind compute.  This module packages the standard
double-buffering discipline so every out-of-VMEM kernel in the repo shares
one implementation (``block_compact``'s streaming variant is the first user;
the planned HBM-streaming ``group_filter_agg`` is written against the same
surface):

  * a staging scratch of :data:`NBUF` tile slots lives in VMEM as
    ``[NBUF, rows, width]``; a slot is a whole tile, so indexing the
    leading axis keeps every slot aligned to the TPU's sublane tiling
    whatever ``rows`` is;
  * :func:`emit_tile` stages tile ``seq`` into slot ``seq % NBUF`` and
    starts its async copy — the DMA of tile ``seq`` is in flight while the
    caller computes tile ``seq + 1``, which is the whole point;
  * re-staging a slot first waits for the DMA launched :data:`NBUF`
    emissions ago, so a slot is never overwritten under an active copy;
  * :func:`drain` settles every outstanding copy — call it before the
    kernel (or grid step) ends, since scratch DMA semaphores must read
    zero when the kernel completes.

Semaphore-wait fine print: ``make_async_copy(...).wait()`` decrements the
semaphore by the descriptor's *size*, so waits are reconstructed with the
current slot's source slice and ANY same-shaped destination slice — the wait
does not need to name the exact destination the in-flight copy targeted.
Every helper here relies on that, which is why a pipeline must emit
same-shaped tiles throughout its lifetime.

Usage sketch (inside a kernel body)::

    # pallas_call(..., scratch_shapes=[*emit_slots(c, w), ...])
    def kernel(..., out_hbm_ref, stage_ref, sem_ref):
        def step(seq, ...):
            tile = ...                              # [c, w] in registers
            emit_tile(stage_ref, sem_ref, seq, tile,
                      out_hbm_ref.at[:, pl.ds(seq * w, w)])
            return seq + 1
        seq = ...loop over step...
        drain(stage_ref, sem_ref, seq, out_hbm_ref.at[:, pl.ds(0, w)])

``emit_tile`` is side-effecting only — callers own the ``seq`` counter (a
traced i32) and advance it themselves, which keeps the helper usable under
``pl.when`` for conditional emission (advance ``seq`` with ``jnp.where`` on
the same predicate).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Staging depth.  Two slots give full overlap of one in-flight DMA with one
#: tile of compute; deeper buffers only help when compute per tile is far
#: cheaper than the copy, which none of our emitters are.
NBUF = 2

def emit_slots(rows: int, width: int, dtype) -> tuple:
    """The two ``scratch_shapes`` entries an emit pipeline needs.

    Returns ``(vmem_stage, dma_semaphores)`` for a ``[rows, width]`` tile
    shape: an ``[NBUF, rows, width]`` staging buffer plus one DMA semaphore
    per slot.  Splat into ``pallas_call(scratch_shapes=[...])`` and pass the
    resulting two refs to :func:`emit_tile` / :func:`drain`.
    """
    return (
        pltpu.VMEM((NBUF, rows, width), dtype),
        pltpu.SemaphoreType.DMA((NBUF,)),
    )


def emit_tile(stage_ref, sem_ref, seq, tile, dst) -> None:
    """Stage ``tile`` (emission number ``seq``) and start its DMA to ``dst``.

    ``seq`` is the caller-owned emission counter (traced i32, starting at
    0); ``dst`` is a ref slice with ``tile``'s exact shape.  If the slot is
    being reused (``seq >= NBUF``) the copy launched ``NBUF`` emissions ago
    is waited first.  Side-effecting only: safe under ``pl.when``; the
    caller advances ``seq`` itself.
    """
    slot = jax.lax.rem(seq, NBUF)
    src = stage_ref.at[slot]

    @pl.when(seq >= NBUF)
    def _settle_previous():
        pltpu.make_async_copy(src, dst, sem_ref.at[slot]).wait()

    stage_ref[slot] = tile
    pltpu.make_async_copy(src, dst, sem_ref.at[slot]).start()


def drain(stage_ref, sem_ref, seq, dst_like) -> None:
    """Wait for every copy still in flight after ``seq`` total emissions.

    ``dst_like`` is any destination slice of the pipeline's tile shape (the
    wait only uses its size — see the module docstring).  Must run before
    the kernel or grid step finishes so no scratch semaphore is left armed.
    """
    for k in range(NBUF):

        @pl.when(seq > k)
        def _settle(k=k):
            slot = jax.lax.rem(seq - 1 - k, NBUF)
            pltpu.make_async_copy(
                stage_ref.at[slot], dst_like, sem_ref.at[slot]
            ).wait()
