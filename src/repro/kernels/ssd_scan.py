"""SSD intra-chunk kernel (Mamba2), Pallas TPU.

Computes, for one (batch, chunk, head) grid cell:
  y     = (C B^T (.) decay (.) dt) @ x        [Q, P]   (causal within chunk)
  state = x^T-weighted outer sum               [P, N]   (chunk's outgoing state)

dt and the per-step log-decay (dta = dt * A[h]) arrive pre-transposed to
[B, H, S] so the kernel's last-axis tile is the Q chunk (lane-aligned when
Q >= 128; Q=64 chunks still lower, padded). B/C are shared across heads
(ngroups=1), expressed by an index_map that ignores the head coordinate —
Pallas keeps the tile resident in VMEM across the H-inner grid steps.

The inter-chunk recurrence (a [B, H, P, N] running state over nc steps) is
sequential-by-construction and stays as a lax.scan in ops.py; this kernel
covers the O(S·Q·(N+P)) intra-chunk work, which dominates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, b_ref, c_ref, dt_ref, dta_ref, y_ref, st_ref, *, q):
    x = x_ref[0, :, 0, :].astype(jnp.float32)  # [Q, P]
    bm = b_ref[0, :, :].astype(jnp.float32)  # [Q, N]
    cm = c_ref[0, :, :].astype(jnp.float32)  # [Q, N]
    dt = dt_ref[0, 0, :].astype(jnp.float32)  # [Q]
    dta = dta_ref[0, 0, :].astype(jnp.float32)  # [Q]

    lcum = jnp.cumsum(dta)  # [Q]
    l_last = lcum[q - 1]

    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, Q]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # decay(i, j) = exp(lcum_i - lcum_j) for i >= j, else 0
    ldiff = lcum[:, None] - lcum[None, :]
    decay = jnp.where(rows >= cols, jnp.exp(ldiff), 0.0)
    m = cb * decay * dt[None, :]  # [Q, Q]
    y_ref[0, :, 0, :] = jax.lax.dot_general(
        m, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(y_ref.dtype)

    seg = jnp.exp(l_last - lcum) * dt  # [Q]
    xw = x * seg[:, None]  # [Q, P]
    st_ref[0, 0, 0, :, :] = jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(st_ref.dtype)  # [P, N]


def ssd_intra(
    x: jax.Array,  # [B, S, H, P]
    bmat: jax.Array,  # [B, S, N]
    cmat: jax.Array,  # [B, S, N]
    dt: jax.Array,  # [B, S, H] f32 (post-softplus)
    a: jax.Array,  # [H] f32 (negative)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y [B, S, H, P] f32, chunk_states [B, nc, H, P, N] f32)."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q

    dt_t = jnp.moveaxis(dt, -1, 1).astype(jnp.float32)  # [B, H, S]
    dta_t = dt_t * a[None, :, None].astype(jnp.float32)

    grid = (b, nc, h)
    y, st = pl.pallas_call(
        functools.partial(_kernel, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, 1, p), lambda b_, c, h_: (b_, c, h_, 0)),
            pl.BlockSpec((1, q, n), lambda b_, c, h_: (b_, c, 0)),
            pl.BlockSpec((1, q, n), lambda b_, c, h_: (b_, c, 0)),
            pl.BlockSpec((1, 1, q), lambda b_, c, h_: (b_, h_, c)),
            pl.BlockSpec((1, 1, q), lambda b_, c, h_: (b_, h_, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, 1, p), lambda b_, c, h_: (b_, c, h_, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda b_, c, h_: (b_, c, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
    )(x, bmat, cmat, dt_t, dta_t)
    return y, st
