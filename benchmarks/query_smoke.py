"""Query-perf smoke: fused vs unfused plans, value-diffed and timed.

Two halves, both in interpret mode (CPU CI):

  1. **Equivalence** — for every query x scale, run the unfused jnp plan and
     the fused kernel plan, then (a) byte-diff the canonical JSON of every
     integer-exact output (group counts, Q1's quantity sums and their
     ratios, all of Q12's conditional counts — f32 accumulates these
     without rounding) and (b) bound the relative error of the float
     product-sums by FLOAT_RTOL (blocked kernel accumulation and
     segment_sum add in different orders; the values cannot be bit-equal
     and anything beyond ~1e-4 is a real bug, not ulps).  Same for the
     pushdown plans' qualifying-row counts.  Any mismatch fails the job.
  2. **Perf trajectory** — run the dbms and pushdown boxes (hot mode)
     through the sweep executor on both impls and record ``items_per_s``
     per (workload, query/plan, scale, impl) into BENCH_5.json, so fused
     vs unfused finally has data points per commit.  The job asserts the
     fused q1 plan at scale >= 0.1 is at least as fast as the unfused one
     on some platform (the tentpole's headline win); interpret-mode wall
     clock is NOT kernel speed, but the fused plan's single-pass shape
     already beats the unfused segment_sum graph on CPU too.

Usage: python -m benchmarks.query_smoke [--out BENCH_5.json] [--iters N]
       [--min-time S] [--platforms cpu-host dpu-sim]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

# Float product-sum tolerance: blocked accumulation vs segment_sum order
# drifts up to ~5e-4 relative at 600k f32 rows (worst on sum_disc: 77k
# ~0.05-magnitude addends into a ~4e3 sum); 1e-3 flags real bugs, not ulps.
FLOAT_RTOL = 1e-3

# Outputs that are integer-exact in f32 (counts, integer sums, and exact
# ratios of those): byte-diffed with NO tolerance.
EXACT_KEYS = {
    "q1": ("count", "sum_qty", "avg_qty"),
    "q6": ("rows",),
    "q12": ("high_line_count", "low_line_count", "count"),
}

DBMS_SCALES = ["0.001", "0.01", "0.1"]
PUSHDOWN_SCALES = ["0.01", "0.1"]
SELECTIVITIES = [0.01, 0.1, 0.5]


def _canon_exact(res: dict, keys) -> str:
    return json.dumps(
        {k: [float(x) for x in np.asarray(res[k], np.float64).reshape(-1)] for k in keys},
        sort_keys=True,
    )


def check_query_equivalence() -> list[str]:
    """Diff fused vs unfused results; returns mismatch descriptions."""
    from repro.engine import datagen, queries

    key = jax.random.PRNGKey(3)
    failures = []
    for scale, rows in [("0.001", 6_000), ("0.01", 60_000), ("0.1", 600_000)]:
        li = datagen.lineitem(key, rows=rows)
        od = datagen.orders(key, rows=max(rows // 4, 256))
        for qname in ("q1", "q6", "q12"):
            call = (lambda f: f(li, od)) if qname == "q12" else (lambda f: f(li))
            unfused = call(jax.jit(queries.QUERIES[qname]))
            fused = call(jax.jit(queries.FUSED_QUERIES[qname]))
            tag = f"dbms/{qname}@{scale}"
            if set(unfused) != set(fused):
                failures.append(f"{tag}: result keys differ {set(unfused) ^ set(fused)}")
                continue
            exact_keys = EXACT_KEYS[qname]
            a = _canon_exact(unfused, exact_keys)
            b = _canon_exact(fused, exact_keys)
            if a.encode() != b.encode():
                failures.append(
                    f"{tag}: exact outputs differ\n  unfused={a}\n  fused  ={b}"
                )
                continue
            worst = 0.0
            for k in unfused:
                if k in exact_keys:
                    continue
                u = np.asarray(unfused[k], np.float64).reshape(-1)
                f = np.asarray(fused[k], np.float64).reshape(-1)
                rel = float(np.max(np.abs(u - f) / np.maximum(np.abs(u), 1e-12)))
                worst = max(worst, rel)
                if rel > FLOAT_RTOL:
                    failures.append(f"{tag}: {k} drifted {rel:.2e} > {FLOAT_RTOL:g}")
            print(f"# {tag}: exact outputs byte-equal, float sums within {worst:.1e}")
    return failures


def check_pushdown_equivalence() -> list[str]:
    """All pushdown plans must report the same qualifying-row count."""
    from repro.engine import datagen, ops
    from repro.tasks.pushdown import _pred_bounds, aggregate_at_data

    key = jax.random.PRNGKey(7)
    failures = []
    for scale, rows in [("0.01", 60_000), ("0.1", 600_000)]:
        table = datagen.lineitem(key, rows=rows)
        scanned = table.select(
            "l_shipdate", "l_extendedprice", "l_discount", "l_quantity"
        )
        for sel in SELECTIVITIES:
            lo, hi = _pred_bounds(sel)
            cap = max(1024, int(1.5 * sel * rows))
            mask = ops.pred_between(scanned["l_shipdate"], lo, hi)
            baseline = int(ops.masked_count(mask))
            _, cnt_j = ops.compact(scanned, mask, cap)
            _, cnt_k = ops.compact(scanned, mask, cap, use_pallas=True)
            cnt_f = int(aggregate_at_data(table, lo, hi)[1])
            counts = {"baseline": baseline, "pushdown": int(cnt_j),
                      "pushdown+kernel": int(cnt_k), "pushdown_kernel": cnt_f}
            if len(set(counts.values())) != 1:
                failures.append(f"pushdown@{scale} sel={sel}: counts diverge {counts}")
            else:
                print(f"# pushdown@{scale} sel={sel}: all plans count {baseline}")
    return failures


def measure_boxes(platforms, iters, min_time, workers):
    """Run the dbms + pushdown perf boxes; returns BENCH entries."""
    from repro.core.box import Box
    from repro.core.executor import SweepExecutor

    executor = SweepExecutor(
        platforms=platforms,
        workers=workers,
        iters=iters,
        warmup=1,
        min_time_s=min_time,
    )
    boxes = [
        Box.from_dict(
            {
                "name": "query_smoke_dbms",
                "tasks": [
                    {
                        "task": "dbms",
                        "params": {
                            "scale": DBMS_SCALES,
                            "query": ["q1", "q6", "q12"],
                            "mode": ["hot"],
                            "impl": ["unfused", "fused"],
                        },
                        "metrics": ["items_per_s", "avg_latency_us"],
                    }
                ],
            }
        ),
        Box.from_dict(
            {
                "name": "query_smoke_pushdown",
                "tasks": [
                    {
                        "task": "pushdown",
                        "params": {
                            "scale": PUSHDOWN_SCALES,
                            "selectivity": [0.1],
                            "plan": ["baseline", "pushdown", "pushdown_kernel"],
                            "impl": ["jnp", "kernel"],
                        },
                        "metrics": ["items_per_s"],
                    }
                ],
            }
        ),
    ]
    entries = []
    for box in boxes:
        res = executor.run_box(box)
        if res.errors:
            for e in res.errors:
                print(f"ERROR {e['task']} {e['params']}: {e['error']}", file=sys.stderr)
            raise SystemExit(f"{box.name}: {len(res.errors)} unit error(s)")
        for r in res.results:
            entries.append(
                {
                    "workload": r.task,
                    "query": r.params.get("query") or r.params.get("plan"),
                    "scale": r.params.get("scale"),
                    "impl": r.params.get("impl", "unfused"),
                    "selectivity": r.params.get("selectivity"),
                    "platform": r.platform,
                    "items_per_s": r.metrics.get("items_per_s"),
                }
            )
    return entries


def assert_fused_wins(entries) -> str | None:
    """The tentpole claim: fused q1 >= unfused at scale >= 0.1 somewhere."""
    best = None
    for e in entries:
        if e["workload"] != "dbms" or e["query"] != "q1":
            continue
        if float(e["scale"]) < 0.1:
            continue
        peer = next(
            (
                p
                for p in entries
                if p["workload"] == "dbms"
                and p["query"] == "q1"
                and p["scale"] == e["scale"]
                and p["platform"] == e["platform"]
                and p["impl"] != e["impl"]
            ),
            None,
        )
        if e["impl"] == "fused" and peer is not None:
            ratio = e["items_per_s"] / max(peer["items_per_s"], 1e-12)
            print(f"# q1@{e['scale']} {e['platform']}: fused/unfused = {ratio:.2f}x")
            if best is None or ratio > best:
                best = ratio
    if best is None:
        return "no fused/unfused q1 pair at scale >= 0.1 was measured"
    if best < 1.0:
        return f"fused q1 never reached unfused throughput (best {best:.2f}x)"
    return None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmarks.query_smoke")
    p.add_argument("--out", default="BENCH_5.json")
    p.add_argument("--iters", type=int, default=2)
    p.add_argument("--min-time", type=float, default=0.2)
    p.add_argument("--workers", type=int, default=1)
    p.add_argument(
        "--platforms", nargs="+", default=["cpu-host"],
        help="execution platforms to record (e.g. cpu-host dpu-sim)",
    )
    args = p.parse_args(argv)

    t0 = time.time()
    failures = check_query_equivalence() + check_pushdown_equivalence()

    entries = measure_boxes(args.platforms, args.iters, args.min_time, args.workers)
    perf_failure = assert_fused_wins(entries)
    if perf_failure:
        failures.append(perf_failure)

    Path(args.out).write_text(
        json.dumps(
            {
                "bench": "query_smoke",
                "float_rtol": FLOAT_RTOL,
                "equivalence_failures": failures,
                "entries": entries,
            },
            indent=1,
        )
        + "\n"
    )
    print(f"# wrote {args.out}: {len(entries)} perf entries in {time.time() - t0:.1f}s")
    for f in failures:
        print(f"FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
