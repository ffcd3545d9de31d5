"""Relational operators in pure JAX (jit-compiled, shardable).

TPU-idiomatic choices:
  * filters evaluate to masks, and downstream aggregates are mask-weighted —
    compaction (gather of qualifying rows) is available but optional, since
    masked reduction avoids dynamic shapes entirely;
  * group-by is segment_sum over dictionary-coded keys (static cardinality);
  * joins are FK index-joins when the build side is dense-keyed, else
    sort-merge (argsort + searchsorted) — both collective-friendly under
    SPMD row sharding.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import tracing
from repro.engine.table import Table


# ---------------------------------------------------------------------------
# Predicates -> masks.
def pred_between(col: jax.Array, lo, hi) -> jax.Array:
    return (col >= lo) & (col < hi)


def pred_in(col: jax.Array, values: tuple) -> jax.Array:
    m = jnp.zeros(col.shape, bool)
    for v in values:
        m = m | (col == v)
    return m


def filter_mask(table: Table, *preds: Callable[[Table], jax.Array]) -> jax.Array:
    mask = jnp.ones((table.num_rows,), bool)
    for p in preds:
        mask = mask & p(table)
    return mask


def compact(
    table: Table, mask: jax.Array, max_rows: int, use_pallas: bool = False,
    stream: str = "auto",
) -> tuple[Table, jax.Array]:
    """Gather qualifying rows into a fixed-size buffer (static shapes).

    Rows beyond max_rows are dropped; returns (table, count). This is the
    'return qualified tuples' half of predicate pushdown — the network
    payload is max_rows-bounded rather than data-dependent.

    ``use_pallas=True`` routes through the fused ``block_compact`` kernel
    (one pass: per-block mask count + prefix-offset scatter) instead of
    ``nonzero`` + one gather per column; only 1-D columns whose values are
    exactly representable in f32 survive the kernel's column matrix, so the
    caller selects the scanned columns first (the pushdown plan does).  The
    kernel copies +0 and finite values with ``2^-103 <= |x| < 2^127`` bit
    for bit (the lineitem columns lie well inside); ``-0.0`` comes back as
    ``+0.0`` and a NaN or inf poisons the slots of its 512-row sub-tile.
    ``stream`` passes through to the kernel wrapper: ``"auto"`` keeps small
    capacities on the VMEM-resident kernel and switches to the HBM-streaming
    kernel once the output buffer would blow the VMEM budget, so
    ``max_rows`` is memory-bounded rather than VMEM-bounded.
    """
    if use_pallas:
        from repro.kernels import ops as kops

        names = table.names
        colmat = jnp.stack([table[n].astype(jnp.float32) for n in names])
        packed, cnt = kops.block_compact(colmat, mask, max_rows, stream=stream)
        out = Table(
            {n: packed[i].astype(table[n].dtype) for i, n in enumerate(names)}
        )
        return out, cnt
    idx = jnp.nonzero(mask, size=max_rows, fill_value=table.num_rows)[0]
    in_range = idx < table.num_rows
    safe = jnp.where(in_range, idx, 0)
    out = table.take(safe)
    # zero out the slots past the real count so payloads are deterministic
    out = Table({n: jnp.where(_bmask(in_range, c.ndim), c, 0) for n, c in out.columns.items()})
    return out, jnp.sum(mask.astype(jnp.int32))


def _bmask(m: jax.Array, ndim: int) -> jax.Array:
    return m.reshape(m.shape + (1,) * (ndim - 1))


# ---------------------------------------------------------------------------
# Pushdown across chips.
def shard_compact(
    table: Table, lo, hi, cap: int, mesh: Mesh, axis: str = "x", use_pallas: bool = True,
) -> tuple[Table, jax.Array]:
    """Predicate pushdown over a table whose rows are sharded over ``axis``
    of ``mesh`` in table order: each owner compacts its own rows with
    ``lo <= l_shipdate < hi`` (:func:`compact`, ``cap`` rows), and only the
    capacity buffers and the counts cross chips, to the consumer, the owner
    of shard 0.  Each other owner's buffer and count reach the consumer
    once, in a ``ppermute`` of their own.

    Returns ``(slots, counts)``, each sharded over ``axis`` in blocks of
    ``S`` rows; the consumer's block, the first, is the result.  Row ``s``
    of a column of ``slots`` (``[S * S, cap]``) holds owner ``s``'s
    compacted rows in table order, zero past its count, and ``counts[s]``
    (``[S * S]``) is its mask population: an owner whose count exceeds
    ``cap`` has overflowed and kept its first ``cap`` rows.  The other
    chips' blocks lead with their own buffer and count and are not part of
    the result.
    """
    s = mesh.shape[axis]

    def to_consumer(x):
        return jnp.stack([x] + [jax.lax.ppermute(x, axis, [(k, 0)]) for k in range(1, s)])

    def owner(t, lo, hi):
        out, cnt = compact(t, pred_between(t["l_shipdate"], lo, hi), cap, use_pallas=use_pallas)
        return jax.tree.map(to_consumer, (out, cnt))

    # The kernels' out_shape carries no varying-axes type: check_vma off.
    return jax.shard_map(
        owner, mesh=mesh, in_specs=(P(axis), P(), P()), out_specs=P(axis), check_vma=False
    )(table, lo, hi)


def _scan(table, lo, hi, *, cap, mesh, axis, use_pallas):
    """shard_compact, and per chip its block's (total, overflowed owners)."""
    slots, counts = shard_compact(table, lo, hi, cap, mesh, axis, use_pallas)
    s = mesh.shape[axis]
    blocks = counts.reshape(s, s)
    return slots, counts, jnp.stack([jnp.sum(blocks, 1), jnp.sum(blocks > cap, 1)], axis=1)


class ShardScan:
    """The sharded pushdown plan as its caller drives it: one jitted
    :func:`shard_compact` for a mesh and a per-owner capacity.

    A call launches one scan (span ``pushdown.launch``), waits for the
    total count and the number of overflowed owners, the only values the
    host reads (span ``pushdown.count``), and adds the request, the
    consumer's inbound bytes and the overflows to :attr:`exchange`.
    """

    def __init__(self, mesh: Mesh, cap: int, use_pallas: bool = True):
        (axis,) = mesh.axis_names  # a 1-D mesh of owners
        self.mesh, self.cap, self.axis = mesh, cap, axis
        self.consumer = mesh.devices.flat[0]
        self.exchange = tracing.Exchange()
        self._run = jax.jit(
            functools.partial(_scan, cap=cap, mesh=mesh, axis=axis, use_pallas=use_pallas),
            out_shardings=NamedSharding(mesh, P(axis)),
        )

    def bytes_per_request(self, table: Table) -> int:
        """The consumer's inbound bytes: every other owner's capacity
        buffer and count."""
        row = sum(table[n].dtype.itemsize for n in table.names)
        return (self.mesh.shape[self.axis] - 1) * (self.cap * row + 4)

    def __call__(self, table: Table, lo: float, hi: float) -> tuple[Table, jax.Array, int]:
        """``(slots, counts, total)``: the consumer's ``[S, cap]`` block of
        each column and its ``[S]`` per-owner counts, left on the consumer,
        and the counts' sum on the host."""
        with tracing.span("pushdown.launch"):
            slots, counts, summary = self._run(table, np.float32(lo), np.float32(hi))
        with tracing.span("pushdown.count"):
            total, overflows = (int(v) for v in jax.device_get(self._on_consumer(summary))[0])
        self.exchange.requests += 1
        self.exchange.bytes_exchanged += self.bytes_per_request(table)
        self.exchange.overflows += overflows
        slots = Table({n: self._on_consumer(c) for n, c in slots.columns.items()})
        return slots, self._on_consumer(counts), total

    def _on_consumer(self, col: jax.Array) -> jax.Array:
        return next(sh.data for sh in col.addressable_shards if sh.device == self.consumer)


# ---------------------------------------------------------------------------
# Aggregation.
def masked_sum(col: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.sum(jnp.where(mask, col.astype(jnp.float32), 0.0))


def masked_count(mask: jax.Array) -> jax.Array:
    return jnp.sum(mask.astype(jnp.int32))


def group_aggregate(
    keys: jax.Array,  # [N] int32 codes in [0, num_groups)
    values: dict[str, jax.Array],  # named value columns
    mask: jax.Array,  # [N] bool
    num_groups: int,
) -> dict[str, jax.Array]:
    """Per-group sums + counts. Returns {name: [num_groups] f32} + "count"."""
    w = mask.astype(jnp.float32)
    out = {
        name: jax.ops.segment_sum(col.astype(jnp.float32) * w, keys, num_segments=num_groups)
        for name, col in values.items()
    }
    out["count"] = jax.ops.segment_sum(w, keys, num_segments=num_groups)
    return out


# ---------------------------------------------------------------------------
# Joins.
def fk_index_join(
    fact: Table, fk_col: str, dim: Table, pk_col: str, carry: tuple[str, ...]
) -> Table:
    """Foreign-key join where dim[pk_col] == arange(len(dim)) (dense keys):
    a pure gather — the fastest join a columnar engine can do."""
    idx = fact[fk_col]
    cols = {n: jnp.take(dim[n], idx, axis=0) for n in carry}
    return fact.with_columns(**cols)


def sort_merge_join(
    left: Table, lkey: str, right: Table, rkey: str, carry: tuple[str, ...]
) -> tuple[Table, jax.Array]:
    """Inner join, right side keys unique. Returns (left + carried right
    columns, match mask). Sort the right side, binary-search each left key."""
    order = jnp.argsort(right[rkey])
    rk_sorted = right[rkey][order]
    pos = jnp.searchsorted(rk_sorted, left[lkey])
    pos = jnp.clip(pos, 0, rk_sorted.shape[0] - 1)
    matched = rk_sorted[pos] == left[lkey]
    cols = {n: jnp.take(right[n][order], pos, axis=0) for n in carry}
    return left.with_columns(**cols), matched


# ---------------------------------------------------------------------------
# Order/top-k.
def top_k(table: Table, col: str, k: int, descending: bool = True) -> Table:
    v = table[col]
    v = v if descending else -v
    _, idx = jax.lax.top_k(v, k)
    return table.take(idx)
