"""Fused scan+filter+aggregate kernel (the predicate-pushdown hot loop).

dpBento's predicate-pushdown module scans table tuples and returns only the
qualifying work (paper §3.5.1 / Fig. 13). On TPU the profitable fusion is
scan -> predicate -> masked aggregate in one VMEM pass: columns stream
HBM->VMEM once, the mask never materializes in HBM, and the reduction
accumulates in a revisited [1, 128] output tile (TPU grids iterate
sequentially, so a running accumulator across blocks is safe).

The aggregate pattern matches TPC-H Q6: SUM(col2 * col3) + COUNT(*) WHERE
lo <= col0 < hi AND lo2 <= col1 < hi2. Bounds arrive via SMEM (scalars).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _kernel(bounds_ref, cols_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lo, hi, lo2, hi2 = bounds_ref[0], bounds_ref[1], bounds_ref[2], bounds_ref[3]
    c0 = cols_ref[0, :]
    c1 = cols_ref[1, :]
    c2 = cols_ref[2, :]
    c3 = cols_ref[3, :]
    mask = (c0 >= lo) & (c0 < hi) & (c1 >= lo2) & (c1 < hi2)
    prod = jnp.where(mask, c2.astype(jnp.float32) * c3.astype(jnp.float32), 0.0)
    cnt = mask.astype(jnp.float32)
    # lane 0 accumulates sum, lane 1 count; remaining lanes stay zero
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    upd = jnp.where(lane == 0, jnp.sum(prod), jnp.where(lane == 1, jnp.sum(cnt), 0.0))
    out_ref[...] += upd


def filter_agg(
    cols: jax.Array,  # [4, N] f32 — (filter0, filter1, value-a, value-b)
    lo: float,
    hi: float,
    lo2: float,
    hi2: float,
    *,
    block_n: int = 16384,
    interpret: bool = False,
) -> jax.Array:
    """Returns [2] f32: (SUM(c2*c3 | mask), COUNT(mask))."""
    _, n = cols.shape
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    bounds = jnp.asarray([lo, hi, lo2, hi2], jnp.float32)

    out = pl.pallas_call(
        _kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((4, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(bounds, cols)
    return out[0, :2]
