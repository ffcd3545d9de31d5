"""Jit'd public wrappers over the Pallas kernels.

The kernels target the TPU.  On a TPU backend they always compile for the
chip; on the CPU backend (the test suite) they run in Pallas interpret mode;
any other backend is an error.  Each op pads awkward shapes up to tile
multiples (or refuses shapes its kernel cannot take) and exposes a
``use_pallas=False`` escape hatch that routes to the ref oracle, so callers
choose the oracle explicitly, never by accident of shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.block_compact import SUB as _COMPACT_SUB
from repro.kernels.block_compact import block_compact as _compact_kernel
from repro.kernels.block_compact import (
    resident_bytes,
    stream_chunk as _stream_chunk,
    stream_finalize as _stream_finalize,
    stream_init as _stream_init,
)
from repro.kernels.decode_attention import decode_attention as _decode_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.group_filter_agg import group_filter_agg as _group_kernel
from repro.kernels.moe_gmm import gmm as _gmm_kernel
from repro.kernels.ssd_scan import ssd_intra as _ssd_kernel


def interpret_mode() -> bool:
    """Whether the kernels run in Pallas interpret mode: never on a TPU,
    always on the CPU; other backends are refused."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run on a TPU or, interpreted, on the CPU; backend is {backend!r}"
    )


def _pad_to(x: jax.Array, axis: int, mult: int) -> tuple[jax.Array, int]:
    n = x.shape[axis]
    target = -(-n // mult) * mult
    if target == n:
        return x, n
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads), n


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "use_pallas"))
def flash_attention(
    q, k, v, *, causal: bool = True, block_q: int = 512, block_k: int = 512,
    use_pallas: bool = True,
):
    """[B, Sq, Hq, dh] x [B, Sk, Hkv, dh]^2 -> [B, Sq, Hq, dh]."""
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal)
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    if q.shape[1] % bq or k.shape[1] % bk:
        raise ValueError(
            f"flash_attention: sequence lengths {q.shape[1]}/{k.shape[1]} are not "
            f"multiples of the blocks {bq}/{bk}; pass use_pallas=False for the oracle"
        )
    return _flash_kernel(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=interpret_mode()
    )


@functools.partial(jax.jit, static_argnames=("block_k", "use_pallas"))
def decode_attention(q, k, v, kv_len, *, block_k: int = 512, use_pallas: bool = True):
    """q [B, Hq, dh], cache [B, S, Hkv, dh], kv_len [B] -> [B, Hq, dh]."""
    if not use_pallas:
        return ref.decode_attention_ref(q, k, v, kv_len)
    k_p, s0 = _pad_to(k, 1, min(block_k, k.shape[1]))
    v_p, _ = _pad_to(v, 1, min(block_k, v.shape[1]))
    return _decode_kernel(
        q, k_p, v_p, kv_len.astype(jnp.int32), block_k=block_k, interpret=interpret_mode()
    )


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def ssd_intra(x, bmat, cmat, dt, a, *, chunk: int = 128, use_pallas: bool = True):
    """Intra-chunk SSD; see kernels/ssd_scan.py. Falls back to a vmapped oracle."""
    if not use_pallas:
        _, s, _, _ = x.shape
        q = min(chunk, s)
        nc = s // q
        ys, sts = [], []
        for c in range(nc):
            sl = slice(c * q, (c + 1) * q)
            y, st = ref.ssd_intra_ref(x[:, sl], bmat[:, sl], cmat[:, sl], dt[:, sl], a)
            ys.append(y)
            sts.append(st)
        return jnp.concatenate(ys, 1), jnp.stack(sts, 1)
    return _ssd_kernel(x, bmat, cmat, dt, a, chunk=chunk, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d", "use_pallas"))
def gmm(lhs, rhs, *, block_c: int = 256, block_f: int = 256, block_d: int = 512,
        use_pallas: bool = True):
    """[E, C, d] x [E, d, f] -> [E, C, f]."""
    if not use_pallas:
        return ref.gmm_ref(lhs, rhs)
    e, c, d = lhs.shape
    f = rhs.shape[-1]
    bc, bf, bd = min(block_c, c), min(block_f, f), min(block_d, d)
    # Zero padding is exact: padded d contributes nothing, padded c/f are cut.
    lhs, _ = _pad_to(lhs, 1, bc)
    lhs, _ = _pad_to(lhs, 2, bd)
    rhs, _ = _pad_to(rhs, 1, bd)
    rhs, _ = _pad_to(rhs, 2, bf)
    out = _gmm_kernel(lhs, rhs, block_c=bc, block_f=bf, block_d=bd, interpret=interpret_mode())
    return out[:, :c, :f]


def _pad_group_rows(cols, keys, block_n: int):
    """Pad the row axis to a whole number of blocks.  Padded rows carry key
    -1: they match no group whatever the predicate program evaluates to on
    the zero-filled columns."""
    keys = keys.reshape(1, -1).astype(jnp.int32)
    n = cols.shape[1]
    bn = min(block_n, n)
    target = -(-n // bn) * bn
    if target != n:
        cols = jnp.pad(cols, ((0, 0), (0, target - n)))
        keys = jnp.pad(keys, ((0, 0), (0, target - n)), constant_values=-1)
    return cols, keys, bn


@functools.partial(
    jax.jit, static_argnames=("num_groups", "block_n", "use_pallas")
)
def group_filter_agg_multi(
    cols, keys, pred_ops, pred_consts, agg_ops, agg_consts, *,
    num_groups: int, block_n: int = 16384, use_pallas: bool = True,
):
    """Single-pass grouped filter+aggregate over a [C, N] column block, for
    B constant sets of one program in one pass.

    ``pred_ops``/``pred_consts``/``agg_ops``/``agg_consts`` encode the
    predicate and aggregate programs (see kernels/group_filter_agg.py —
    ``encode_predicates`` / ``encode_aggregates`` build them).
    ``pred_consts``/``agg_consts`` carry a leading program dimension
    (``[B, K, 2]`` / ``[B, A, MAX_TERMS]``) and are *traced inputs*, not
    trace-time constants — one compiled executable serves any predicate
    bounds of the same query shape; a single query is B = 1.  Returns
    ``[B, num_groups, A + 1]``: per-group aggregate sums, then the masked
    count.  Each slot visits the data blocks in the same order, so slot
    ``b`` matches a B = 1 call with that program's constants: counts
    exactly, float sums to within the order of the additions inside one
    block's dot, which the compiler may choose differently for different B.
    """
    if not use_pallas:
        return ref.group_filter_agg_multi_ref(
            cols, keys, pred_ops, pred_consts, agg_ops, agg_consts, num_groups
        )
    cols, keys, bn = _pad_group_rows(cols, keys, block_n)
    return _group_kernel(
        cols, keys, pred_ops, pred_consts, agg_ops, agg_consts,
        num_groups=num_groups, block_n=bn, interpret=interpret_mode(),
    )


#: VMEM the resident block_compact may spend on its padded [C, cap] output
#: before ``stream="auto"`` switches to the HBM-streaming variant.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


@functools.partial(
    jax.jit, static_argnames=("cap", "block_n", "stream", "chunk_n", "use_pallas")
)
def block_compact(
    cols, mask, cap: int, *,
    block_n: int = 65536,
    stream: str = "auto",
    chunk_n: int = 1 << 21,
    use_pallas: bool = True,
):
    """Compact the masked rows of a [C, N] block into a [C, cap] buffer.

    Returns (out, count): ``out[:, j]`` is the j-th qualifying row for
    ``j < min(count, cap)``, zero beyond; ``count`` is the total mask
    population.  One fused pass instead of ``nonzero`` + per-column gather.

    ``stream`` picks the kernel variant: ``"never"`` is the VMEM-resident
    kernel (cap bounded by :data:`VMEM_BUDGET_BYTES`), ``"always"`` the
    HBM-streaming kernel (cap bounded by HBM), and ``"auto"`` (default)
    streams exactly when the resident output would blow the budget — so
    callers never lose the small-cap fast path.  Streamed inputs longer
    than ``chunk_n`` rows are split across kernel invocations with the
    offset/count state carried between calls (the chunked driver).
    """
    if not use_pallas:
        return ref.block_compact_ref(cols, mask, cap)
    mask = (mask.reshape(1, -1) != 0).astype(jnp.int32)
    c, n = cols.shape
    # Blocks must hold whole sub-tiles; pad the tail with mask=0 rows.
    bn = min(-(-block_n // _COMPACT_SUB) * _COMPACT_SUB,
             -(-n // _COMPACT_SUB) * _COMPACT_SUB)
    target = -(-n // bn) * bn
    if target != n:
        cols = jnp.pad(cols, ((0, 0), (0, target - n)))
        mask = jnp.pad(mask, ((0, 0), (0, target - n)))
    if stream == "auto":
        stream = "always" if resident_bytes(c, cap) > VMEM_BUDGET_BYTES else "never"
    if stream == "never":
        return _compact_kernel(cols, mask, cap, block_n=bn, interpret=interpret_mode())
    if stream != "always":
        raise ValueError(f"stream must be auto/always/never, got {stream!r}")
    # Chunked driver: one streaming-kernel invocation per chunk_n rows, the
    # (out, state, carry) triple threaded through input_output_aliases so
    # every chunk lands in one HBM allocation.
    cn = max(bn, (chunk_n // bn) * bn)
    state = _stream_init(c, cap)
    for s in range(0, target, cn):
        e = min(s + cn, target)
        state = _stream_chunk(
            state, cols[:, s:e], mask[:, s:e], cap,
            block_n=bn, interpret=interpret_mode(),
        )
    return _stream_finalize(state, cap)
