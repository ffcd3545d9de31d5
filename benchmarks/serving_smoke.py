"""Serving smoke: scan-sharing equality + shed-free service below saturation.

Three checks, all on CPU (interpret mode) so CI can run them:

  1. **Scan-sharing oracle** — for every query, a micro-batch of requests
     with different predicate constants through the multi-program kernel
     must equal serial per-request execution (both pallas and ref paths):
     counts exactly, float sums within ``FLOAT_RTOL`` (interpret mode lets
     XLA reorder the additions inside a block's dot by an ulp or so).
  2. **Shed-free below saturation** — measure each (query, platform)
     point's closed-loop saturation QPS, then offer a fixed-rate open-loop
     load at a fraction of it for ``--duration`` seconds; admission
     control must shed nothing and every offered request must complete.
  3. **Record** — p50/p99 latency, delivered QPS, and saturation QPS per
     (query, platform) go to BENCH_6.json for trend tracking.

Usage: python -m benchmarks.serving_smoke [--out BENCH_6.json]
       [--duration 10] [--platforms cpu-host] [--load-fraction 0.4]
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import time
from pathlib import Path

import jax
import numpy as np

QUERIES = ("q1", "q6", "q12")
ROWS = 6_000  # scale 0.001: small enough for interpret-mode CI, real kernels
FLOAT_RTOL = 1e-6


def check_scan_sharing() -> list[str]:
    """Diff micro-batched vs serial fused-query results."""
    from repro.engine import datagen, queries
    from repro.runtime.loadgen import sample_params

    li = datagen.lineitem(jax.random.PRNGKey(3), rows=ROWS)
    od = datagen.orders(jax.random.PRNGKey(3), rows=max(ROWS // 4, 256))
    plans = queries.make_serving_plans(li, od)
    failures = []
    rng = random.Random(0)
    for qname in QUERIES:
        param_list = [sample_params(qname, rng) for _ in range(6)]
        for use_pallas in (True, False):
            batched = queries.fused_query_batch(
                plans[qname], param_list, use_pallas=use_pallas
            )
            for i, (params, got) in enumerate(zip(param_list, batched)):
                want = queries.fused_query_serial(
                    plans[qname], params, use_pallas=use_pallas
                )
                for k in want:
                    w, g = np.asarray(want[k]), np.asarray(got[k])
                    same = (
                        np.array_equal(w, g) if k in queries.COUNT_KEYS
                        else np.allclose(g, w, rtol=FLOAT_RTOL, atol=0)
                    )
                    if not same:
                        failures.append(
                            f"{qname}[{i}] pallas={use_pallas}: {k} differs "
                            f"(batched != serial)"
                        )
        mode = "pallas+ref"
        print(f"# {qname}: {len(param_list)}-request micro-batch equals serial ({mode})")
    return failures


def serve_point(plans, qname: str, duration_s: float, load_fraction: float):
    """One (query) serving run: saturation probe, then sub-saturation load."""
    from repro.runtime.loadgen import generate_trace
    from repro.runtime.serve_query import QueryServer, measure_saturation, run_open_loop

    saturation = measure_saturation(plans, [qname], max_batch=8, seed=0)
    # Offer a comfortable fraction of the measured ceiling so the shed-free
    # assertion holds on however slow a CI machine this lands on.
    rate = max(1.0, load_fraction * saturation)
    server = QueryServer(plans, queue_depth=256, max_batch=8)
    server.warmup([qname])
    trace = generate_trace([qname], rate, duration_s, arrival="fixed", seed=0)
    report = run_open_loop(server, trace)
    lat = sorted(report.latencies_s)
    return {
        "query": qname,
        "rate_qps": rate,
        "saturation_qps": saturation,
        "offered": report.offered,
        "completed": len(report.completed),
        "shed": report.shed,
        "p50_latency_us": 1e6 * float(np.percentile(lat, 50)) if lat else None,
        "p99_latency_us": 1e6 * float(np.percentile(lat, 99)) if lat else None,
        "qps": report.qps,
        "kernel_calls": server.kernel_calls,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmarks.serving_smoke")
    p.add_argument("--out", default="BENCH_6.json")
    p.add_argument("--duration", type=float, default=10.0)
    p.add_argument(
        "--load-fraction", type=float, default=0.4,
        help="offered fixed rate as a fraction of measured saturation",
    )
    p.add_argument(
        "--platforms", nargs="+", default=["cpu-host"],
        help="platforms to record (rates on simulated platforms are "
        "dilated by their time_scale)",
    )
    args = p.parse_args(argv)

    t0 = time.time()
    failures = check_scan_sharing()

    from repro.core.platform import get_platform
    from repro.engine import datagen, queries

    li = datagen.lineitem(jax.random.PRNGKey(3), rows=ROWS)
    od = datagen.orders(jax.random.PRNGKey(3), rows=max(ROWS // 4, 256))
    plans = queries.make_serving_plans(li, od)

    entries = []
    # Serve each query once on the host; simulated platforms reuse the
    # measurement under their time dilation (one 10s wall-clock run per
    # query keeps the job's budget bounded).
    for qname in QUERIES:
        base = serve_point(plans, qname, args.duration / len(QUERIES), args.load_fraction)
        if base["shed"] != 0:
            failures.append(
                f"{qname}: shed {base['shed']} request(s) at "
                f"{base['rate_qps']:.0f} qps below saturation "
                f"({base['saturation_qps']:.0f} qps)"
            )
        if base["completed"] != base["offered"]:
            failures.append(
                f"{qname}: only {base['completed']}/{base['offered']} "
                f"offered requests completed"
            )
        for plat in args.platforms:
            ts = float(get_platform(plat).time_scale)
            entries.append(
                {
                    **base,
                    "platform": plat,
                    "rate_qps": base["rate_qps"] / ts,
                    "saturation_qps": base["saturation_qps"] / ts,
                    "qps": base["qps"] / ts,
                    "p50_latency_us": (
                        base["p50_latency_us"] * ts if base["p50_latency_us"] else None
                    ),
                    "p99_latency_us": (
                        base["p99_latency_us"] * ts if base["p99_latency_us"] else None
                    ),
                }
            )
        print(
            f"# {qname}: saturation {base['saturation_qps']:.0f} qps, served "
            f"{base['completed']}/{base['offered']} at {base['rate_qps']:.0f} qps, "
            f"p99 {base['p99_latency_us'] and round(base['p99_latency_us'])} us, "
            f"shed {base['shed']}"
        )

    Path(args.out).write_text(
        json.dumps(
            {"bench": "serving_smoke", "failures": failures, "entries": entries},
            indent=1,
        )
        + "\n"
    )
    print(f"# wrote {args.out}: {len(entries)} entries in {time.time() - t0:.1f}s")
    for f in failures:
        print(f"FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
