"""Grouped (per-expert) matmul kernel: [E, C, d] x [E, d, f] -> [E, C, f].

Classic tiled matmul with an expert (group) grid dim: grid
(E, C/bc, F/bf, D/bd), the contraction dim innermost with a f32 VMEM
accumulator. Tile defaults (bc, bf, bd) = (256, 256, 512) keep
256x512 + 512x256 operand tiles + 256x256 acc ~= 0.9 MB in VMEM and all
MXU dims at multiples of 128.

This is the expert-FFN hot loop for the MoE archs (kimi-k2: E=384 experts
of [7168 -> 2048]); the dispatch scatter/gather stays in XLA where the SPMD
partitioner can fuse it with the surrounding collectives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(lhs_ref, rhs_ref, out_ref, acc_ref, *, nd):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[0, :, :],
        rhs_ref[0, :, :],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(di == nd - 1)
    def _flush():
        out_ref[0, :, :] = acc_ref[...].astype(out_ref.dtype)


def gmm(
    lhs: jax.Array,  # [E, C, d]
    rhs: jax.Array,  # [E, d, f]
    *,
    block_c: int = 256,
    block_f: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = lhs.shape
    _, _, f = rhs.shape
    bc, bf, bd = min(block_c, c), min(block_f, f), min(block_d, d)
    assert c % bc == 0 and f % bf == 0 and d % bd == 0, (lhs.shape, rhs.shape, (bc, bf, bd))
    nd = d // bd

    grid = (e, c // bc, f // bf, nd)
    return pl.pallas_call(
        functools.partial(_kernel, nd=nd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e_, ci, fi, di: (e_, ci, di)),
            pl.BlockSpec((1, bd, bf), lambda e_, ci, fi, di: (e_, di, fi)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e_, ci, fi, di: (e_, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), lhs.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lhs, rhs)
