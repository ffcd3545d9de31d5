"""Causal GQA flash-attention forward, Pallas TPU.

Grid (B, Hq, nQ, nK) — nK innermost, sequential ("arbitrary") so the online
softmax state lives in VMEM scratch across K blocks. Q/K/V tiles are pulled
HBM->VMEM by BlockSpec; GQA is expressed in the K/V index_map (query head h
reads KV head h // group). Causal skipping is a @pl.when on the block's
visibility, so fully-masked tiles cost no MXU work.

Block sizes default to (512, 512): VMEM per step =
q (512x128 f32) + k/v (2x) + acc (512x128 f32) + m/l ~= 1 MB << 16 MB VMEM,
and 512 is a multiple of the 128-lane register width.

Masked lanes use a large-negative (-1e30) instead of -inf so rows with no
visible keys produce zeros, never NaNs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # m/l scratch replicated across the lane dim


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, bq, bk, nk, causal):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Visibility: causal block (qi*bq .. qi*bq+bq-1) sees keys < qi*bq+bq.
    visible = jnp.bool_(True) if not causal else (ki * bk <= qi * bq + bq - 1)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)  # [bq, dh]
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # [bk, dh]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_scr[:, :1]  # [bq, 1] (lanes replicated)
        l_prev = l_scr[:, :1]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)  # [bq, bk]
        l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _flush():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0, :, 0, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, Sq, Hq, dh]
    k: jax.Array,  # [B, Sk, Hkv, dh]
    v: jax.Array,  # [B, Sk, Hkv, dh]
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, dh = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    nq, nk = sq // bq, sk // bk
    if causal:
        assert sq == sk, "causal flash kernel expects square attention"

    grid = (b, hq, nq, nk)
    kern = functools.partial(
        _kernel, scale=dh**-0.5, bq=bq, bk=bk, nk=nk, causal=causal
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, dh), lambda b_, h, qi, ki: (b_, qi, h, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda b_, h, qi, ki, g=g: (b_, ki, h // g, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda b_, h, qi, ki, g=g: (b_, ki, h // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, dh), lambda b_, h, qi, ki: (b_, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # m
            pltpu.VMEM((bq, LANES), jnp.float32),  # l
            pltpu.VMEM((bq, dh), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
