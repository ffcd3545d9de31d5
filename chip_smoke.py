"""Smoke run of the query engine's main path on a TPU chip.

    python chip_smoke.py                # one chip, the main path at TPC-H SF1
    python chip_smoke.py --four-chips   # four chips, the network collectives

The one-chip run generates lineitem (6,000,000 rows) and orders (1,500,000
rows) on the device from ``--seed`` and drives, through the entry points a
user calls:

* fused q1/q6/q12 (``engine.queries.FUSED_QUERIES``, one
  ``group_filter_agg`` pass each) and the unfused jnp plans
  (``engine.queries.QUERIES``);
* the query server (``runtime.serve_query.QueryServer``, ``max_batch=8``)
  over a fixed-rate open-loop trace mixing q1/q6/q12, so batches of 2-8
  requests share one ``group_filter_agg_multi`` pass;
* pushdown compaction (``engine.ops.compact(..., use_pallas=True)``) at
  selectivity 0.01 (the VMEM-resident kernel) and 0.5 (the HBM-streaming
  kernel, chunked) against ``kernels.ref.block_compact_ref``.

Query results are checked against a float64 numpy evaluation of the same
query on a host copy of the tables (:func:`_reference`): counts and
conditional counts exactly, float sums within a relative deviation of
``RTOL``.  The unfused plans are held to the same exact counts; their float
sums accumulate in f32 (``segment_sum``), so their deviation is reported,
not bounded.  Compacted rows must match bit for bit.  The four-chip run
builds the 1-D mesh of ``launch.mesh.mesh_1d`` over four devices and checks all_reduce and
all_gather under ``schedule=xla`` and ``schedule=shardmap`` at 32 MB against
numpy.

Each phase prints one JSON line (compile seconds, run seconds, largest
deviation from its reference).  The last line is
``{"ok": true, "device": {...}}`` only when every phase passed; the script
exits non-zero otherwise, and at once when JAX finds no TPU.  Everything runs
in this one process: a chip belongs to the process that opened it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

LINEITEM_ROWS = 6_000_000
ORDERS_ROWS = 1_500_000
RTOL = 1e-4  # float sums, relative to max(|reference|, 1)
COLLECTIVE_RTOL = 1e-3  # a 32 MB f32 sum of arange, against numpy's float64
SERVE_QUERIES = ("q1", "q6", "q12")
SERVE_PER_QUERY = 16
SERVE_RATE = 2000.0  # req/s: far above one request's service rate, so batches form


def _require(ok, detail) -> None:
    """A result check that, unlike ``assert``, also holds under ``python -O``."""
    if not ok:
        raise AssertionError(detail)


def _device_or_exit(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU, JAX backend is {devices[0].platform!r}", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"chip_smoke: needs {chips} chips, JAX sees {len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices


def _import_repro() -> None:
    """Import the program from this checkout's ``src`` and nowhere else."""
    if not (SRC / "repro" / "kernels" / "ops.py").is_file():
        print(f"chip_smoke: the program is not at {SRC}", file=sys.stderr)
        raise SystemExit(2)
    sys.path.insert(0, str(SRC))
    from repro.kernels import ops

    _require(Path(ops.__file__).resolve().is_relative_to(SRC), ops.__file__)


def _block(x):
    import jax

    return jax.block_until_ready(x)


def _timed(fn, *args):
    """(output, compile seconds, run seconds) of one call of a jitted ``fn``."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    t1 = time.perf_counter()
    out = _block(compiled(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _deviation(got: dict, want: dict, rtol: float | None = RTOL) -> float:
    """Largest relative deviation of the float entries of a result dict.

    Raises on a missing key, a non-finite value, any mismatch of an exact
    key, or a float deviation beyond ``rtol`` (``None`` bounds nothing).
    """
    import numpy as np

    from repro.engine.queries import COUNT_KEYS

    worst = 0.0
    _require(set(got) == set(want), (sorted(got), sorted(want)))
    for k in want:
        g = np.asarray(got[k], np.float64)
        w = np.asarray(want[k], np.float64)
        _require(g.shape == w.shape, (k, g.shape, w.shape))
        _require(np.all(np.isfinite(g)), k)
        if k in COUNT_KEYS:
            _require(np.array_equal(g, w), (k, g, w))
            continue
        dev = float(np.max(np.abs(g - w) / np.maximum(np.abs(w), 1.0)))
        _require(rtol is None or dev <= rtol, (k, dev, g, w))
        worst = max(worst, dev)
    return worst


def _reference(q: str, li: dict, od: dict, **params) -> dict:
    """q1/q6/q12 in float64 numpy over host columns: the semantics of
    ``engine.queries.QUERIES``, with its predicate constants rounded to f32
    as the device plans compare them."""
    import numpy as np

    from repro.engine import datagen, queries

    f32, f64 = np.float32, np.float64
    price = li["l_extendedprice"].astype(f64)
    disc = li["l_discount"].astype(f64)
    if q == "q1":
        cutoff = f32(datagen.date(1998, 12, 1) - params.get("delta_days", 90.0))
        m = li["l_shipdate"] <= cutoff
        keys = (li["l_returnflag"] * 2 + li["l_linestatus"])[m]
        disc_price = price * (1.0 - disc)
        vals = {
            "sum_qty": li["l_quantity"], "sum_base_price": price, "sum_disc_price": disc_price,
            "sum_charge": disc_price * (1.0 + li["l_tax"].astype(f64)), "sum_disc": disc,
        }
        out = {k: np.bincount(keys, weights=np.asarray(v, f64)[m], minlength=6) for k, v in vals.items()}
        out["count"] = np.bincount(keys, minlength=6).astype(f64)
        cnt = np.maximum(out["count"], 1.0)
        out["avg_qty"] = out["sum_qty"] / cnt
        out["avg_price"] = out["sum_base_price"] / cnt
        out["avg_disc"] = out["sum_disc"] / cnt
        return out
    year = params.get("year", 1994)
    lo, hi = f32(datagen.date(year)), f32(datagen.date(year + 1))
    if q == "q6":
        d = params.get("discount", 0.06)
        m = (
            (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
            & (li["l_discount"] >= f32(d - 0.011)) & (li["l_discount"] < f32(d + 0.011))
            & (li["l_quantity"] < f32(params.get("qty", 24.0)))
        )
        return {"revenue": np.sum(price[m] * disc[m]), "rows": np.sum(m)}
    _require(q == "q12", q)
    prio = od["o_orderpriority"][li["l_orderkey"]]
    m = (
        np.isin(li["l_shipmode"], queries.Q12_SHIPMODES)
        & (li["l_commitdate"] < li["l_receiptdate"]) & (li["l_shipdate"] < li["l_commitdate"])
        & (li["l_receiptdate"] >= lo) & (li["l_receiptdate"] < hi)
    )
    groups = len(datagen.SHIPMODE)
    return {
        "high_line_count": np.bincount(li["l_shipmode"][m & (prio <= 1)], minlength=groups).astype(f64),
        "low_line_count": np.bincount(li["l_shipmode"][m & (prio > 1)], minlength=groups).astype(f64),
        "count": np.bincount(li["l_shipmode"][m], minlength=groups).astype(f64),
    }


def _phase(name: str, fn, failed: list[str]) -> None:
    from repro.kernels import ops as kops

    try:
        stats = fn()
        _require(not kops.interpret_mode(), "the kernels ran in interpret mode")
        print(json.dumps({"phase": name, "interpret": False, **stats, "passed": True}), flush=True)
    except Exception:  # noqa: BLE001 - every phase runs; any failure fails the script
        traceback.print_exc()
        print(json.dumps({"phase": name, "passed": False}), flush=True)
        failed.append(name)


# ---------------------------------------------------------------------------
def _one_chip(seed: int) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import datagen, ops, queries
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.runtime.loadgen import generate_trace
    from repro.runtime.serve_query import QueryServer, run_open_loop
    from repro.tasks import pushdown

    failed: list[str] = []
    data: dict = {}

    def gen():
        k_li, k_od = jax.random.split(jax.random.PRNGKey(seed))
        t0 = time.perf_counter()
        data["li"] = _block(datagen.lineitem(k_li, rows=LINEITEM_ROWS, num_orders=ORDERS_ROWS))
        data["od"] = _block(datagen.orders(k_od, rows=ORDERS_ROWS))
        return {"rows": LINEITEM_ROWS, "orders": ORDERS_ROWS, "run_s": time.perf_counter() - t0}

    _phase("datagen", gen, failed)
    if failed:
        return failed
    li, od = data["li"], data["od"]
    host = ({n: np.asarray(c) for n, c in li.columns.items()},
            {n: np.asarray(c) for n, c in od.columns.items()})

    def fused(q):
        args = (li, od) if q == "q12" else (li,)
        got, c_s, r_s = _timed(jax.jit(queries.FUSED_QUERIES[q]), *args)
        unfused = _block(jax.jit(queries.QUERIES[q])(*args))
        want = _reference(q, *host)
        return {
            "compile_s": c_s, "run_s": r_s, "max_rel_dev": _deviation(got, want),
            "unfused_max_rel_dev": _deviation(unfused, want, rtol=None),
        }

    for q in SERVE_QUERIES:
        _phase(f"fused_{q}", lambda q=q: fused(q), failed)

    def serve():
        plans = queries.make_serving_plans(li, od)
        server = QueryServer(plans, max_batch=8)
        t0 = time.perf_counter()
        server.warmup()
        warm_s = time.perf_counter() - t0
        n = SERVE_PER_QUERY * len(SERVE_QUERIES)
        trace = generate_trace(list(SERVE_QUERIES), SERVE_RATE, n / SERVE_RATE, arrival="fixed", seed=seed)
        report = run_open_loop(server, trace)
        done = len(report.completed)
        _require(report.shed == 0 and done == len(trace), (report.shed, done))
        sizes = [c.batch_size for c in report.completed]
        _require(max(sizes) >= 2, sizes)
        params = {r.uid: r.params for r in trace}
        worst, bit_equal = 0.0, 0
        for c in report.completed:
            p = params[c.uid]
            args = (li, od) if c.query == "q12" else (li,)
            worst = max(worst, _deviation(c.result, _reference(c.query, *host, **p)))
            _deviation(c.result, queries.QUERIES[c.query](*args, **p), rtol=None)
            serial = queries.fused_query_serial(plans[c.query], p)
            bit_equal += all(np.array_equal(np.asarray(serial[k]), np.asarray(c.result[k])) for k in serial)
        lat = sorted(report.latencies_s)
        return {
            "compile_s": warm_s, "run_s": report.duration_s, "requests": len(trace),
            "kernel_calls": server.kernel_calls, "max_batch": max(sizes),
            "p50_latency_s": lat[len(lat) // 2], "max_latency_s": lat[-1],
            "bit_equal_to_serial": f"{bit_equal}/{len(trace)}", "max_rel_dev": worst,
        }

    _phase("serving", serve, failed)

    def compact(sel):
        scanned = li.select("l_shipdate", "l_extendedprice", "l_discount", "l_quantity")
        lo, hi = pushdown._pred_bounds(sel)
        cap = max(1024, int(1.5 * sel * LINEITEM_ROWS))

        def plan(t):
            return ops.compact(t, ops.pred_between(t["l_shipdate"], lo, hi), cap, use_pallas=True)

        (out, cnt), c_s, r_s = _timed(jax.jit(plan), scanned)
        colmat = jnp.stack([scanned[n] for n in scanned.names])
        mask = ops.pred_between(scanned["l_shipdate"], lo, hi)
        want, want_cnt = _block(jax.jit(ref.block_compact_ref, static_argnums=2)(colmat, mask, cap))
        _require(int(cnt) == int(want_cnt), (int(cnt), int(want_cnt)))
        for i, n in enumerate(scanned.names):
            _require(np.array_equal(np.asarray(out[n]), np.asarray(want[i])), n)
        streamed = kops.resident_bytes(len(scanned.names), cap) > kops.VMEM_BUDGET_BYTES
        return {
            "cap": cap, "count": int(cnt), "kernel": "stream" if streamed else "resident",
            "compile_s": c_s, "run_s": r_s, "max_rel_dev": 0.0,
        }

    for sel in (0.01, 0.5):
        _phase(f"compact_sel{sel}", lambda sel=sel: compact(sel), failed)
    return failed


def _four_chips() -> list[str]:
    import numpy as np

    from repro.launch.mesh import mesh_1d
    from repro.tasks import network

    failed: list[str] = []
    mesh = mesh_1d()
    _require(mesh.size == 4, mesh.size)

    def run(kind, schedule):
        fn, x = network.collective(mesh, kind, schedule, network._SIZES["32MB"])
        got, c_s, r_s = _timed(fn, x)
        got = np.asarray(got, np.float64)
        xs = np.arange(x.size, dtype=np.float64)
        if kind == "all_gather":
            want = xs + 1.0 if schedule == "xla" else np.tile(xs, mesh.size)
            _require(np.array_equal(got, want), (kind, schedule))
            dev = 0.0
        else:
            if schedule == "xla":
                want = np.full_like(xs, xs.sum())
            else:
                want = np.tile(xs.reshape(mesh.size, -1).sum(0), mesh.size)
            _require(got.shape == want.shape, (got.shape, want.shape))
            dev = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))
            _require(dev <= COLLECTIVE_RTOL, dev)
        return {"bytes": 4 * x.size, "devices": mesh.size, "compile_s": c_s, "run_s": r_s, "max_rel_dev": dev}

    for kind in ("all_reduce", "all_gather"):
        for schedule in ("xla", "shardmap"):
            _phase(f"{kind}_{schedule}", lambda k=kind, s=schedule: run(k, s), failed)
    return failed


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-chip collectives path")
    p.add_argument("--seed", type=int, default=0, help="seed of the generated tables")
    args = p.parse_args(argv)

    chips = 4 if args.four_chips else 1
    devices = _device_or_exit(chips)
    _import_repro()
    from repro.core.device import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    failed = _four_chips() if args.four_chips else _one_chip(args.seed)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
