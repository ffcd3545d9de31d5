"""Predicate pushdown: a storage-side scan of lineitem that filters on the
ship date and compacts the qualifying rows of the four scanned columns
through the program's ``engine.ops.compact(..., use_pallas=True)``.

Set-up generates lineitem on the device from the seed (the serving
configuration's table for the same seed, all of its columns held on the
device as a storage node holds the table it serves) and compiles the scan
plan for the cell's capacity, ``cap_factor * selectivity * rows``.  In the
window one client keeps one scan in flight: it draws the next ship-date
window from the seed, runs the plan over the four scanned columns, and reads
the count back to the host; the compacted rows stay on the device.
Afterwards :func:`checks` compares every request's count with
:class:`bench.reference.ScanReference`, and all rows of a sample of requests
drawn from the seed, whose outputs are held until then.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen, loadgen, reference
from bench.harness import Cell, Check, Outcome, Window, say
from bench.trace import Spans


def plan(cap: int):
    """The scan the window drives: ``lo <= l_shipdate < hi``, compacted."""
    from repro.engine import ops

    def scan(table, lo, hi):
        return ops.compact(table, ops.pred_between(table["l_shipdate"], lo, hi), cap, use_pallas=True)

    return jax.jit(scan)


def checks(sent: list[tuple[float, float, int]], got: dict[int, np.ndarray], ref, cap: int,
           keep: int) -> list[Check]:
    """The scan checks: ``sent[j]`` is a request's (lo, hi, count read
    back) and ``got[j]`` the ``[4, cap]`` rows it compacted, for the
    ``keep`` requests sampled for the row check."""
    counts_wrong = sum(c != ref.count(lo, hi) for lo, hi, c in sent)
    cells_wrong = 0
    for j, rows in got.items():
        lo, hi, _ = sent[j]
        cells_wrong += int(np.sum(rows.view(np.uint32) != ref.rows(lo, hi, cap).view(np.uint32)))
    return [
        Check("requests_with_wrong_count", counts_wrong, 0),
        Check("compacted_values_wrong", cells_wrong, 0),
        Check("requests_row_checked_missing", keep - len(got) if len(sent) >= keep else 0, 0),
    ]


def run(cell: Cell, seed: int, seconds: float, window: Window, spans: Spans) -> Outcome:
    from repro.engine.table import Table

    cfg, traffic = cell.config, cell.traffic
    state: dict = {}
    t = time.perf_counter()
    n, num_orders = datagen.rows(cfg["scale"])
    k_li, _ = jax.random.split(datagen.key(seed))
    state["lineitem"] = jax.block_until_ready(datagen.lineitem(k_li, n, num_orders))
    state["scanned"] = Table({c: state["lineitem"][c] for c in reference.SCAN_COLUMNS})
    say(setup="datagen", seconds=time.perf_counter() - t, lineitem_rows=n)

    t = time.perf_counter()
    cap = int(cfg["cap_factor"] * traffic["selectivity"] * n)
    scan = plan(cap)
    windows = loadgen.scan_windows(traffic, seed)
    lo, hi = next(loadgen.scan_windows(traffic, seed + 1))
    int(scan(state["scanned"], jnp.float32(lo), jnp.float32(hi))[1])
    say(setup="warmup", seconds=time.perf_counter() - t, cap=cap)

    keep = int(cfg["requests_row_checked"])
    pick = loadgen.rng(seed, "row-sample")
    sent: list[tuple[float, float, int]] = []  # (lo, hi, count read back)
    held: dict[int, tuple] = {}  # request -> compacted columns kept for the row check
    took: list[float] = []  # host seconds of each request, count included
    with window():
        t0 = time.perf_counter()
        while (t := time.perf_counter()) - t0 < seconds:
            lo, hi = next(windows)
            with spans("scan.request"):
                out, cnt = scan(state["scanned"], jnp.float32(lo), jnp.float32(hi))
            with spans("scan.fetch_count"):
                count = int(cnt)
            sent.append((lo, hi, count))
            took.append(time.perf_counter() - t)
            # Reservoir sample of `keep` requests, uniform over the window.
            j = len(sent) - 1
            slot = j if j < keep else pick.randrange(j + 1)
            if slot < keep:
                if j >= keep:
                    del held[sorted(held)[slot]]
                held[j] = tuple(out[c] for c in reference.SCAN_COLUMNS)
            del out
        end = time.perf_counter() - t0
    e2e = {"scan_rows_per_s": len(sent) * n / end}
    records = {
        "rows": n, "cap": cap, "columns": len(reference.SCAN_COLUMNS), "counts": [c for _, _, c in sent],
        "compiles_in_window": window.compiles,
    }
    say(window_s=end, requests=len(sent), compiles_in_window=window.compiles,
        median_request_s=float(np.median(took)) if took else 0.0, longest_request_s=max(took, default=0.0))

    def verify() -> list[Check]:
        host = jax.device_get(state["scanned"].columns)
        got = {j: np.stack(cols) for j, cols in jax.device_get(held).items()}
        state.clear()
        held.clear()
        t = time.perf_counter()
        out = checks(sent, got, reference.ScanReference(host), cap, keep)
        say(reference_s=time.perf_counter() - t, rows_checked_requests=sorted(got))
        return out

    def failed(checks: list[Check]) -> int:
        return int(checks[0].value)

    return Outcome(
        window_start=t0, e2e=e2e, attempted=len(sent), records=records, verify=verify, failed=failed,
    )
