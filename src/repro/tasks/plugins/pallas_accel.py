"""Accelerator plugin (paper §5.2, Fig. 6): Pallas kernels as the "ASIC".

The paper probes DPU compression/RegEx engines against CPU SIMD and
multithreading. The TPU analogue: a hand-tiled Pallas kernel (the hardened
unit) vs the XLA-compiled jnp implementation (the general-purpose path)
for three data-path hot-spots: attention, grouped expert matmul, fused
filter+aggregate. Like the paper's accelerators, the kernel has a fixed
launch overhead — small payloads favor the jnp path, large payloads the
kernel (the crossover is the Fig. 6 story).

Plugin-typical caveat: works where Pallas works (TPU, or interpret mode on
CPU); interpret-mode wall-clock is NOT kernel speed — relative numbers
across payload sizes still expose the overhead-vs-throughput shape.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.metrics import Samples
from repro.core.registry import register
from repro.core.task import Task, TaskContext
from repro.core.timing import measure
from repro.kernels import ops as kops
from repro.kernels.group_filter_agg import encode_aggregates, encode_predicates

_SIZES = {"small": 128, "medium": 512, "large": 2048}


@register
class PallasAccelTask(Task):
    name = "pallas_accel"
    param_space = {
        "workload": ["attention", "gmm", "filter_agg"],
        "size": list(_SIZES),
        "impl": ["kernel", "jnp"],
    }
    default_metrics = ("ops_per_s", "avg_latency_us")

    def run(self, ctx: TaskContext, params: dict[str, Any]) -> Samples:
        wl = params.get("workload", "filter_agg")
        s = _SIZES[params.get("size", "medium")]
        use_pallas = params.get("impl", "kernel") == "kernel"
        key = jax.random.PRNGKey(0)

        if wl == "attention":
            b, h, hkv, dh = 1, 4, 2, 64
            q = jax.random.normal(key, (b, s, h, dh), jnp.float32)
            k = jax.random.normal(key, (b, s, hkv, dh), jnp.float32)
            v = jax.random.normal(key, (b, s, hkv, dh), jnp.float32)
            fn = lambda: kops.flash_attention(q, k, v, causal=True, block_q=128,
                                              block_k=128, use_pallas=use_pallas)
            flops = 2.0 * b * h * s * s * dh  # qk + pv, causal halves twice
        elif wl == "gmm":
            e, c, d, f = 4, s, 256, 256
            lhs = jax.random.normal(key, (e, c, d), jnp.float32)
            rhs = jax.random.normal(key, (e, d, f), jnp.float32)
            fn = lambda: kops.gmm(lhs, rhs, block_c=128, block_f=128, block_d=128,
                                  use_pallas=use_pallas)
            flops = 2.0 * e * c * d * f
        else:  # filter_agg: two range predicates, one product-sum, one group
            n = s * 1024
            cols = jax.random.uniform(key, (4, n), jnp.float32)
            keys = jnp.zeros((n,), jnp.int32)
            pred_ops, pred_consts = encode_predicates([("range", 0, 0.2, 0.8), ("range", 1, 0.1, 0.9)])
            agg_ops, agg_consts = encode_aggregates([[("col", 2), ("col", 3)]])
            fn = lambda: kops.group_filter_agg_multi(
                cols, keys, pred_ops, pred_consts[None], agg_ops, agg_consts[None],
                num_groups=1, block_n=16384, use_pallas=use_pallas)
            flops = 6.0 * n  # 4 compares + mul + add

        times = measure(fn, iters=ctx.iters, warmup=ctx.warmup)
        return Samples(times_s=times, ops_per_iter=flops)
