"""Predicate pushdown module task (paper §3.5.1, Fig. 13).

Three plans for ``SELECT ... WHERE lo <= l_shipdate < hi`` over lineitem:

  baseline — fetch-then-filter: a copy of every scanned column stands for
             the move of the whole table to the consumer, and the predicate
             runs after it.  Bytes moved = the scanned columns.
  pushdown — filter at the storage owners: the scanned columns are
             row-sharded over a 1-D mesh of every device of the process
             (``launch.mesh.mesh_1d``), each owner compacts its own
             qualifying rows into a buffer of fixed capacity, and only the
             buffers and counts travel to the consumer, the owner of shard
             0 (``engine.ops.ShardScan``).  Bytes moved = every owner's
             buffer.  ``impl=kernel`` compacts with the ``block_compact``
             Pallas kernel (HBM-streaming past its VMEM budget),
             ``impl=jnp`` with ``nonzero`` + gather.  On one device the
             consumer owns the whole table and nothing crosses chips.
  pushdown_kernel — filter and aggregate at the data in one pass (the Q6
             ``filter_agg`` kernel): only the aggregate travels.

``impl`` is ignored by the other plans.  Params: scale x selectivity.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.metrics import Samples
from repro.core.registry import register
from repro.core.task import Task, TaskContext
from repro.core.timing import measure
from repro.engine import datagen, ops
from repro.launch.mesh import mesh_1d

_SCALES = {"0.01": 60_000, "0.1": 600_000, "1.0": 6_000_000}


def _pred_bounds(selectivity: float) -> tuple[float, float]:
    """shipdate window whose width hits the requested selectivity."""
    lo = datagen.DATE_EPOCH_DAYS
    width = selectivity * datagen.DATE_RANGE_DAYS
    return float(lo), float(lo + width)


def kernel_scan_columns(table) -> jax.Array:
    """[4, N] column matrix for the fused filter_agg plan: shipdate and
    discount as the two filter columns, extendedprice x 1.0 as the value
    product.  The single source for the plan's column layout — the CI smoke
    and tests reuse it so they validate the exact plan the task measures."""
    n = table.num_rows
    return jnp.stack(
        [table["l_shipdate"], table["l_discount"],
         table["l_extendedprice"], jnp.ones((n,), jnp.float32)]
    )


@register
class PushdownTask(Task):
    name = "pushdown"
    param_space = {
        "scale": list(_SCALES),
        "selectivity": [0.01, 0.1, 0.5],
        "plan": ["baseline", "pushdown", "pushdown_kernel"],
        "impl": ["jnp", "kernel"],
    }
    default_metrics = ("items_per_s",)

    def prepare(self, ctx: TaskContext) -> None:
        key = jax.random.PRNGKey(7)
        for name, rows in _SCALES.items():
            ctx.scratch[name] = datagen.lineitem(key, rows=rows)

    def run(self, ctx: TaskContext, params: dict[str, Any]) -> Samples:
        table = ctx.scratch[params.get("scale", "0.01")]
        sel = float(params.get("selectivity", 0.1))
        plan = params.get("plan", "pushdown")
        use_kernel = params.get("impl", "jnp") == "kernel"
        lo, hi = _pred_bounds(sel)
        n = table.num_rows
        cols = ("l_shipdate", "l_extendedprice", "l_discount", "l_quantity")
        scanned = table.select(*cols)

        if plan == "baseline":
            # fetch-then-filter: force a copy of every column (the wire move),
            # then evaluate the predicate on the consumer.
            @jax.jit
            def fn(t):
                moved = jax.tree_util.tree_map(lambda c: c + 0.0, t)  # materialized move
                mask = ops.pred_between(moved["l_shipdate"], lo, hi)
                return ops.masked_sum(moved["l_extendedprice"], mask), ops.masked_count(mask)

            times = measure(fn, scanned, iters=ctx.iters, warmup=ctx.warmup)
            moved_bytes = scanned.nbytes()
            moved_bytes_exact = moved_bytes  # every row moves, no padding
        elif plan == "pushdown":
            # filter at the owners, move only their capacity-bounded buffers
            if "mesh" not in ctx.scratch:
                ctx.scratch["mesh"] = mesh_1d()  # every device of the process
            mesh = ctx.scratch["mesh"]
            owners = mesh.size
            owner_cap = max(1024, int(1.5 * sel * n / owners))
            scan = ops.ShardScan(mesh, owner_cap, use_pallas=use_kernel)
            sharded = jax.device_put(scanned, NamedSharding(mesh, P(scan.axis)))

            def fn(t):
                return scan(t, lo, hi)

            times = measure(fn, sharded, iters=ctx.iters, warmup=ctx.warmup)
            _, counts, _ = fn(sharded)
            # Provisioned traffic: every owner's buffer travels whole.  The
            # exact column charges only the rows that qualified, so Fig. 13
            # can show both.
            moved_bytes = owners * owner_cap * 16  # 4 cols x 4 B per provisioned slot
            moved_bytes_exact = sum(min(int(c), owner_cap) for c in jax.device_get(counts)) * 16
        else:  # pushdown_kernel: fused Pallas filter+aggregate, zero row movement
            from repro.kernels import ops as kops

            colmat = kernel_scan_columns(table)

            def fn(c):
                return kops.filter_agg(c, lo, hi, -1.0, 1.0)

            times = measure(fn, colmat, iters=ctx.iters, warmup=ctx.warmup)
            moved_bytes = 8  # one (sum, count) pair
            moved_bytes_exact = moved_bytes

        return Samples(
            times_s=times,
            items_per_iter=float(n),
            bytes_per_iter=float(moved_bytes),
            extra={
                "selectivity": sel,
                "moved_bytes": float(moved_bytes),
                "moved_bytes_exact": float(moved_bytes_exact),
            },
        )
