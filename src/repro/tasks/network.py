"""Network microbenchmark (paper §3.4.4, Figs. 11-12).

DPU TCP/RDMA maps to ICI/DCN collectives. Parameters: collective kind x
payload bytes x mesh axis. Two schedule families mirror the paper's
TCP-vs-RDMA contrast:
  xla      — jnp ops under jit; the XLA SPMD partitioner schedules the
             collective (the "kernel TCP stack": convenient, generic);
  shardmap — explicit jax.lax.p* inside shard_map (the "kernel-bypass"
             path: the schedule is exactly what you wrote).

The mesh spans every device of the process.  On one device the
collectives degenerate to copies; ``python chip_smoke.py --four-chips``
runs all_reduce and all_gather under both schedules on a four-chip mesh and
checks the results against numpy.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.metrics import Samples
from repro.core.registry import register
from repro.core.task import Task, TaskContext
from repro.core.timing import measure
from repro.launch.mesh import mesh_1d

_SIZES = {"32KB": 1 << 13, "1MB": 1 << 18, "32MB": 1 << 23, "256MB": 1 << 26}  # f32 counts


def collective(mesh: Mesh, kind: str, schedule: str, n: int):
    """The jitted collective and its sharded input, ``arange`` over about
    ``n`` f32 elements (rounded to whole shards) on the 1-D mesh ``x``.

    ``xla`` outputs: all_reduce -> every element is the total sum;
    reduce_scatter -> the same, sharded; all_gather -> ``x + 1``
    replicated; all_to_all/ppermute -> the ``[n_dev, n / n_dev]``
    transpose.  ``shardmap`` outputs are per-device results concatenated
    along ``x``: all_reduce -> the sum of the shards on every device,
    all_gather -> the whole input on every device.
    """
    n_dev = mesh.size
    n = max(n, n_dev)  # at least one element per shard
    n -= n % n_dev
    x = jnp.arange(n, dtype=jnp.float32)
    sharded = jax.device_put(x, NamedSharding(mesh, P("x")))

    if schedule == "xla":
        if kind in ("all_reduce", "reduce_scatter"):
            fn = jax.jit(lambda v: jnp.sum(v) * jnp.ones_like(v),
                         in_shardings=NamedSharding(mesh, P("x")),
                         out_shardings=NamedSharding(mesh, P("x") if kind == "reduce_scatter" else P()))
        elif kind == "all_gather":
            fn = jax.jit(lambda v: v + 1.0,
                         in_shardings=NamedSharding(mesh, P("x")),
                         out_shardings=NamedSharding(mesh, P()))
        else:  # all_to_all / ppermute approximated by a resharding transpose
            m2 = x.reshape(n_dev, n // n_dev)
            sharded = jax.device_put(m2, NamedSharding(mesh, P("x", None)))
            fn = jax.jit(lambda v: v.T,
                         in_shardings=NamedSharding(mesh, P("x", None)),
                         out_shardings=NamedSharding(mesh, P(None, "x")))
        return fn, sharded

    # shardmap: explicit collectives; outputs flattened, out_specs P("x")
    def body(v):
        if kind == "all_reduce":
            return jax.lax.psum(v, "x")
        if kind == "all_gather":
            return jax.lax.all_gather(v, "x", tiled=True).reshape(-1)
        if kind == "reduce_scatter":
            return jax.lax.psum_scatter(v, "x", tiled=True)
        if kind == "all_to_all":
            vv = v.reshape(n_dev, -1)
            out = jax.lax.all_to_all(vv, "x", split_axis=0, concat_axis=0, tiled=False)
            return out.reshape(-1)
        # ppermute: ring shift
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        return jax.lax.ppermute(v, "x", perm)

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
    )
    return fn, sharded


@register
class NetworkTask(Task):
    name = "network"
    param_space = {
        "collective": ["all_reduce", "all_gather", "reduce_scatter", "all_to_all", "ppermute"],
        "payload": list(_SIZES),
        "schedule": ["xla", "shardmap"],
    }
    default_metrics = ("bandwidth_gb_s", "avg_latency_us", "p99_latency_us")

    def prepare(self, ctx: TaskContext) -> None:
        ctx.scratch["mesh"] = mesh_1d()

    def run(self, ctx: TaskContext, params: dict[str, Any]) -> Samples:
        mesh = ctx.scratch["mesh"]
        n_dev = mesh.size
        kind = params.get("collective", "all_reduce")
        fn, sharded = collective(
            mesh, kind, params.get("schedule", "xla"), _SIZES[params.get("payload", "1MB")]
        )
        n = sharded.size
        times = measure(fn, sharded, iters=ctx.iters, warmup=ctx.warmup)
        nbytes = 4.0 * n
        wire = {
            "all_reduce": 2 * (n_dev - 1) / max(n_dev, 1) * nbytes,
            "all_gather": (n_dev - 1) / max(n_dev, 1) * nbytes,
            "reduce_scatter": (n_dev - 1) / max(n_dev, 1) * nbytes,
            "all_to_all": (n_dev - 1) / max(n_dev, 1) * nbytes,
            "ppermute": nbytes,
        }[kind]
        return Samples(
            times_s=times,
            bytes_per_iter=nbytes,
            ops_per_iter=1.0,
            extra={"wire_bytes": wire, "n_devices": float(n_dev)},
        )
