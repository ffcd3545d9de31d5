"""Flash-decoding attention: one query token vs a long KV cache, Pallas TPU.

Grid (B, Hkv, nK) — all G query heads of a KV group are processed together
(q tile [G, dh]), so the MXU sees a [G, dh] x [dh, bk] matmul per step
instead of G rank-1 products. The per-sequence valid length (kv_len) masks
cache tail slots AND gates whole blocks via @pl.when, so a 32k-slot cache
with 1k valid tokens reads ~1k keys, not 32k.

The online-softmax state is [G, LANES] VMEM scratch carried over K blocks
(sequential innermost dim), identical in structure to the prefill kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, bk, nk):
    ki = pl.program_id(2)
    kv_len = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * bk < kv_len)  # skip blocks entirely past the valid length
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)  # [G, dh]
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # [bk, dh]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [G, bk]
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _flush():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # [B, Hq, dh]
    k: jax.Array,  # [B, S, Hkv, dh]
    v: jax.Array,  # [B, S, Hkv, dh]
    kv_len: jax.Array,  # [B] int32
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, hq, dh = q.shape
    _, s, hkv, _ = k.shape
    g = hq // hkv
    bk = min(block_k, s)
    assert s % bk == 0, (s, bk)
    nk = s // bk

    grid = (b, hkv, nk)
    kern = functools.partial(_kernel, scale=dh**-0.5, bk=bk, nk=nk)
    qg = q.reshape(b, hkv, g, dh)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b_, h, ki: (b_,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, dh), lambda b_, h, ki: (b_, h, 0, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda b_, h, ki: (b_, ki, h, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda b_, h, ki: (b_, ki, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda b_, h, ki: (b_, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_len, qg.reshape(b, hkv, g, dh), k, v)
    return out.reshape(b, hq, dh)
