"""The control of ``correct``: the plain reference put in the program's
place with the columns stored one precision below the configuration's
(bfloat16 for its float32), held to the cell's own checks and limits
(:func:`bench.drivers.serve.checks`, :func:`bench.drivers.pushdown.checks`).
It has to come out not correct.

    python3 bench/control.py --workload serve_closed_16 --seeds 11 12 13 --requests 3500

On one chip it generates each seed's tables at the cell's size and answers
``--requests`` of the cell's requests both ways, in two variants:
``all_f32`` rounds every float32 column (dates too) to bfloat16, and
``values`` only the value columns, with keys and dates exact.  It prints
each variant's checks, their limits and ``correct`` per seed.  The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

import ml_dtypes
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT)] + [q for q in sys.path if Path(q or ".").resolve() != ROOT / "bench"]

from bench import datagen, loadgen, reference  # noqa: E402
from bench.drivers import pushdown, serve  # noqa: E402

#: The float32 columns that hold values rather than dates.
VALUE_COLUMNS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax", "o_totalprice")
VARIANTS = ("all_f32", "values")


def _bf16(x):
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def lower(cols: dict, variant: str) -> dict:
    """The columns with the variant's float32 columns rounded to bfloat16."""
    def low(k, v):
        return v.dtype == np.float32 and (variant == "all_f32" or k in VALUE_COLUMNS)

    return {k: _bf16(v) if low(k, v) else v for k, v in cols.items()}


def serving_requests(traffic: dict, seed: int, count: int) -> list[tuple[str, dict]]:
    """The first ``count`` requests of the cell's clients, taken in turn."""
    clients = [loadgen.client_requests(traffic, seed, c) for c in range(traffic["clients"])]
    return list(itertools.islice((next(g) for g in itertools.cycle(clients)), count))


def serving_control(li: dict, od: dict, requests, limits: dict, variant: str) -> list:
    """The serving checks of the reference over the lowered tables."""
    low = reference.ServingReference(lower(li, variant), lower(od, variant))
    results = {uid: low(q, p) for uid, (q, p) in enumerate(requests)}
    return serve.checks(requests, results, reference.ServingReference(li, od), limits)


def scan_control(cols: dict, windows, cap: int, keep: int, variant: str) -> list:
    """The scan checks of the reference over the lowered columns; with
    ``all_f32`` the window's bounds are rounded as well."""
    low = reference.ScanReference(lower(cols, variant))
    bound = _bf16 if variant == "all_f32" else np.float32
    sent = [(lo, hi, low.count(float(bound(lo)), float(bound(hi)))) for lo, hi in windows]
    got = {j: low.rows(float(bound(lo)), float(bound(hi)), cap) for j, (lo, hi, _) in enumerate(sent[:keep])}
    return pushdown.checks(sent, got, reference.ScanReference(cols), cap, keep)


def reading(checks: list) -> dict:
    return {"correct": all(c.ok for c in checks),
            "checks": {c.name: {"value": c.value, "limit": c.limit} for c in checks}}


def main() -> int:
    from bench import run

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--requests", type=int, required=True, help="requests answered per seed, as a run does")
    args = p.parse_args()
    cell = run.load_cell(run.manifest(), args.workload)
    run.require_chips(cell.chips)
    run.enable_compile_cache()
    cfg, traffic = cell.config, cell.traffic
    n, num_orders = datagen.rows(cfg["scale"])
    for seed in args.seeds:
        t = time.perf_counter()
        li, od = datagen.tables(seed, n, num_orders)
        li = {k: np.asarray(v) for k, v in li.items()}
        od = {k: np.asarray(v) for k, v in od.items()}
        out = {}
        for variant in VARIANTS:
            if cfg["driver"] == "serve":
                checks = serving_control(li, od, serving_requests(traffic, seed, args.requests), cfg["limits"],
                                         variant)
            else:
                windows = loadgen.scan_windows(traffic, seed)
                cap = int(cfg["cap_factor"] * traffic["selectivity"] * n)
                checks = scan_control({c: li[c] for c in reference.SCAN_COLUMNS},
                                      [next(windows) for _ in range(args.requests)], cap,
                                      int(cfg["requests_row_checked"]), variant)
            out[variant] = reading(checks)
        print(json.dumps({"workload": args.workload, "seed": seed, "control": out,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
