"""Benchmark orchestrator: run every paper-figure box through the framework.

Usage:
  python -m benchmarks.run                              # all figures
  python -m benchmarks.run --only fig13_pushdown fig15_dbms
  python -m benchmarks.run --iters 5 --warmup 2
  python -m benchmarks.run --workers 4                  # concurrent tests
  python -m benchmarks.run --platforms cpu-host dpu-sim # platform sweep
  python -m benchmarks.run --no-cache                   # force remeasure
  python -m benchmarks.run --shard 0/2                  # one hash-slice of each figure
  python -m benchmarks.run --shard 0/2@0.25             # weighted (cost-balanced) slice
  python -m benchmarks.run --shard 0/2@auto             # weights calibrated from fleet pings
  python -m benchmarks.run --shard 0/2 --shard-plan     # preview shard cost shares
  python -m benchmarks.run --merge                      # reassemble shard CSVs
  python -m benchmarks.run --remote 127.0.0.1:7177      # execute on a worker
  python -m benchmarks.run --remote hostA:7177,hostB:7177 --workers 4
                                                        # dynamic pull across a fleet
  python -m benchmarks.run --schedule static            # up-front LPT plan instead
  python -m benchmarks.run --list

Per figure: expand the box (paper §3.3), execute through the sweep
executor, write results/bench/<figure>.csv, and echo
`figure,task,params...,metric,value` lines to stdout — the combined CSV
consumed by bench_output.txt.  A persistent result cache (default
results/bench/cache.json) makes re-runs incremental: already-measured
(task, params, platform, iters) points are skipped and reported as
`cached=N` in the per-figure/total summary lines.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

from benchmarks.figures import FIGURES

RESULTS = Path(__file__).resolve().parents[1] / "results" / "bench"


def _figure_csv(fig: str, shard=None) -> str:
    return f"{fig}.csv" if shard is None else f"{fig}.shard{shard.index}of{shard.count}.csv"


def run_figure(fig: str, executor, out_dir: Path, shard=None):
    from repro.core.box import Box

    box = Box.from_dict(FIGURES[fig])
    res = executor.run_box(box, shard=shard)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / _figure_csv(fig, shard)).write_text(res.csv())
    return res


def merge_figure(fig: str, out_dir: Path, platforms) -> int:
    """Merge every <fig>.shardIofN.csv in out_dir into <fig>.csv."""
    import re

    from repro.core.box import Box
    from repro.core.report import load_report_rows, merge_shard_reports, to_csv

    by_count: dict[int, list[Path]] = {}
    for f in sorted(out_dir.glob(f"{fig}.shard*of*.csv")):
        m = re.fullmatch(rf"{re.escape(fig)}\.shard(\d+)of(\d+)\.csv", f.name)
        if m:
            by_count.setdefault(int(m.group(2)), []).append(f)
    if not by_count:
        return 0
    if len(by_count) > 1:
        # Stale files from a previous different-N sharding would silently
        # shadow fresh rows; make the operator clean up instead.
        raise SystemExit(
            f"refusing to merge {fig}: shard files from different shard counts "
            f"{sorted(by_count)} coexist in {out_dir}; delete the stale set"
        )
    (count, shard_files), = by_count.items()
    rows = merge_shard_reports(
        [load_report_rows(f) for f in shard_files],
        box=Box.from_dict(FIGURES[fig]),
        platforms=platforms,
    )
    (out_dir / f"{fig}.csv").write_text(to_csv(rows))
    return len(rows)


def main(argv=None) -> int:
    from repro.core import config as config_mod
    from repro.core.device import enable_compile_cache

    p = argparse.ArgumentParser(prog="benchmarks.run")
    p.add_argument("--only", nargs="*", default=None, help="figure ids to run")
    # Shared sweep surface (core.config): same flags as repro.core.runner
    # and the serving CLI, with this orchestrator's defaults.
    config_mod.add_sweep_args(p, iters=3, warmup=1, platforms=["cpu-host"])
    p.add_argument(
        "--merge", action="store_true",
        help="merge existing per-figure shard CSVs into <figure>.csv and exit",
    )
    p.add_argument("--out", default=str(RESULTS))
    p.add_argument("--list", action="store_true")
    args = p.parse_args(argv)
    enable_compile_cache()

    if args.list:
        for fig, box in FIGURES.items():
            n = sum(
                1
                for t in box["tasks"]
                for _ in _expand_count(t.get("params", {}))
            )
            print(f"{fig}: {n} tests over {[t['task'] for t in box['tasks']]}")
        return 0

    figs = args.only or list(FIGURES)
    unknown = set(figs) - set(FIGURES)
    if unknown:
        p.error(f"unknown figures {sorted(unknown)}; known: {sorted(FIGURES)}")

    out_dir = Path(args.out)
    if args.merge:
        for fig in figs:
            n = merge_figure(fig, out_dir, args.platforms)
            print(f"# {fig}: merged {n} rows", file=sys.stderr)
        return 0

    cfg = config_mod.SweepConfig.from_args(args)
    shard = config_mod.validate_sweep(cfg, p.error)
    executor = config_mod.make_executor(cfg, cache_default_path=out_dir / "cache.json")
    if args.shard_plan:
        from repro.core.box import Box

        for fig in figs:
            box = Box.from_dict(FIGURES[fig])
            for row in executor.shard_plan(box, shard):
                print(
                    f"{fig}: shard {row['shard']}  weight {row['weight']:g}  "
                    f"units {row['units']}  est_cost {row['est_cost']:.6g}  "
                    f"share {row['cost_share']:.1%}"
                )
        return 0
    all_errors = []
    total_cached = total_tests = 0
    print("figure,task,params,metric,value")
    t_start = time.time()
    for fig in figs:
        t0 = time.time()
        res = run_figure(fig, executor, out_dir, shard=shard)
        all_errors.extend({**e, "figure": fig} for e in res.errors)
        total_cached += res.stats.cached
        total_tests += res.stats.total
        for row in res.rows:
            task = row.get("task", "?")
            prefix = ";".join(
                f"{k[6:]}={row[k]}" for k in sorted(row) if k.startswith("param:")
            )
            if "platform" in row:
                prefix = f"platform={row['platform']};" + prefix
            for k, v in row.items():
                if k in ("task", "platform") or k.startswith("param:"):
                    continue
                print(f"{fig},{task},{prefix},{k},{v}")
        print(
            f"# {fig}: {len(res.rows)} rows in {time.time() - t0:.1f}s "
            f"({len(res.errors)} errors, cached={res.stats.cached}/{res.stats.total})",
            file=sys.stderr,
        )
    print(
        f"# total {time.time() - t_start:.1f}s cached={total_cached}/{total_tests}",
        file=sys.stderr,
    )
    for e in all_errors:
        print(f"ERROR {e['figure']}/{e['task']} {e['params']}: {e['error']}", file=sys.stderr)
    return 1 if all_errors else 0


def _expand_count(params: dict):
    import itertools

    lists = [v if isinstance(v, list) else [v] for v in params.values()] or [[None]]
    return itertools.product(*lists)


if __name__ == "__main__":
    raise SystemExit(main())
