"""Predicate pushdown over a lineitem sharded across chips: each chip owns
a range of the table's rows, filters them on the ship date where they live,
and ships only the compacted rows of the four scanned columns to one
consumer chip, through the program's ``engine.ops.ShardScan`` (its
``shard_compact`` under ``jax.shard_map``).

Set-up first builds the program's mesh over the host's chips
(``launch.mesh.mesh_1d``) and the scan plan, so that a program without them
fails before any data is made.  It then generates lineitem with the
benchmark's generator under a jit whose output is row-sharded over the
mesh: each chip makes only its own rows, and the table is the one a single
device makes from the seed.  The per-owner capacity is ``cap_factor *
selectivity * rows / shards``.  In the window one client keeps one scan in
flight, as ``bench/drivers/pushdown.py`` does: it draws the next ship-date
window from the seed, runs the plan, and reads the total count back to the
host.  The per-owner counts stay on the consumer until the window closes.
The consumer's slots of a reservoir sample of requests are held until then
on the other chips, one sample a chip (a chip-to-chip copy, span
``scan.sample``): the consumer has no room for them beside the scan, and a
copy to the host would take seconds of the window.  Afterwards
:func:`checks` compares every request's total and per-owner counts with one
:class:`bench.reference.ScanReference` per shard, and every slot of the
sampled requests bit for bit.
"""
from __future__ import annotations

import concurrent.futures
import math
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import datagen, loadgen, reference
from bench.harness import Cell, Check, Outcome, Window, say
from bench.trace import Spans

CHUNK_ROWS = 1 << 21  # rows per streaming block_compact call (kernels.ops chunk_n)


def checks(sent: list[tuple[float, float, int]], owner_counts: list, got: dict[int, np.ndarray],
           refs: list, cap: int, keep: int) -> tuple[list[Check], int]:
    """The sharded scan checks and the number of failed requests.

    ``sent[j]`` is a request's (lo, hi, total count read back),
    ``owner_counts[j]`` its ``[S]`` per-owner counts, ``got[j]`` the
    consumer's ``[S, 4, cap]`` slots of a sampled request, and ``refs[s]``
    the reference over shard ``s``.  A request fails when its total or an
    owner's count is wrong or an owner overflowed ``cap``."""
    bad_total = bad_owner = overflowed = 0
    failed = set()
    for j, ((lo, hi, total), counts) in enumerate(zip(sent, owner_counts)):
        want = [r.count(lo, hi) for r in refs]
        wrong = [total != sum(want), list(map(int, counts)) != want, max(map(int, counts)) > cap]
        bad_total, bad_owner, overflowed = bad_total + wrong[0], bad_owner + wrong[1], overflowed + wrong[2]
        if any(wrong):
            failed.add(j)

    def wrong_cells(js: tuple[int, int]) -> int:
        j, s = js
        lo, hi, _ = sent[j]
        return int(np.sum(got[j][s].view(np.uint32) != refs[s].rows(lo, hi, cap).view(np.uint32)))

    with concurrent.futures.ThreadPoolExecutor(len(refs)) as pool:  # numpy drops the GIL here
        cells_wrong = sum(pool.map(wrong_cells, [(j, s) for j in got for s in range(len(refs))]))
    return [
        Check("requests_with_wrong_total", bad_total, 0),
        Check("requests_with_wrong_owner_count", bad_owner, 0),
        Check("requests_with_overflow", overflowed, 0),
        Check("compacted_values_wrong", cells_wrong, 0),
        Check("requests_row_checked_missing", keep - len(got) if len(sent) >= keep else 0, 0),
    ], len(failed)


def owner_rows(columns: dict[str, jax.Array], shards: int) -> list[dict[str, np.ndarray]]:
    """Each owner's rows of the row-sharded ``columns`` on the host, in
    shard order: every chip's shards are copied at once, and the whole
    table is never assembled in one host array."""
    pieces = {c: sorted(v.addressable_shards, key=lambda p: p.index[0].start or 0) for c, v in columns.items()}
    assert all(len(ps) == shards for ps in pieces.values()), "one shard a chip"
    for ps in pieces.values():
        for p in ps:
            p.data.copy_to_host_async()
    with concurrent.futures.ThreadPoolExecutor(shards) as pool:
        host = {c: list(pool.map(lambda p: np.asarray(p.data), ps)) for c, ps in pieces.items()}
    return [{c: host[c][s] for c in columns} for s in range(shards)]


def run(cell: Cell, seed: int, seconds: float, window: Window, spans: Spans) -> Outcome:
    from repro.engine.ops import ShardScan
    from repro.engine.table import Table
    from repro.launch.mesh import mesh_1d

    cfg, traffic = cell.config, cell.traffic
    shards = int(cfg["shards"])
    mesh = mesh_1d()
    if mesh.size != shards:
        raise RuntimeError(f"the configuration shards the table over {shards} chips, the mesh has {mesh.size}")
    n, num_orders = datagen.rows(cfg["scale"])
    n -= n % shards  # whole shards: SF100's 600,121,500 rows are four of 150,030,375
    cap = int(cfg["cap_factor"] * traffic["selectivity"] * n / shards)
    scan = ShardScan(mesh, cap)

    state: dict = {}
    t = time.perf_counter()
    k_li, _ = jax.random.split(datagen.key(seed))
    generate = jax.jit(datagen.lineitem, static_argnames=("n", "num_orders"),
                       out_shardings=NamedSharding(mesh, P(scan.axis)))
    state["lineitem"] = jax.block_until_ready(generate(k_li, n=n, num_orders=num_orders))
    state["scanned"] = Table({c: state["lineitem"][c] for c in reference.SCAN_COLUMNS})
    say(setup="datagen", seconds=time.perf_counter() - t, lineitem_rows=n, shards=shards)

    t = time.perf_counter()
    windows = loadgen.scan_windows(traffic, seed)
    scan(state["scanned"], *next(loadgen.scan_windows(traffic, seed + 1)))
    say(setup="warmup", seconds=time.perf_counter() - t, cap_per_owner=cap,
        stream_chunks_per_owner=math.ceil(n / shards / CHUNK_ROWS))

    keep = int(cfg["requests_row_checked"])
    pick = loadgen.rng(seed, "row-sample")
    sent: list[tuple[float, float, int]] = []  # (lo, hi, total count read back)
    counts: list[jax.Array] = []  # per request, the [S] per-owner counts on the consumer
    # Reservoir of `keep` (request, the consumer's slots), sample i held on holders[i].
    reservoir: list[tuple[int, dict] | None] = [None] * keep
    holders = [d for d in mesh.devices.flat if d != scan.consumer] or [scan.consumer]
    took: list[float] = []  # host seconds of each request, total count included
    before = scan.exchange.bytes_exchanged, scan.exchange.overflows
    with window():
        t0 = time.perf_counter()
        while (t := time.perf_counter()) - t0 < seconds:
            lo, hi = next(windows)
            with spans("scan.request"):
                slots, owner_counts, total = scan(state["scanned"], lo, hi)
            sent.append((lo, hi, total))
            counts.append(owner_counts)
            took.append(time.perf_counter() - t)
            # Reservoir sample of `keep` requests, uniform over the window.
            j = len(sent) - 1
            slot = j if j < keep else pick.randrange(j + 1)
            if slot < keep:
                reservoir[slot] = None
                with spans("scan.sample"):
                    copy = jax.device_put(slots.columns, holders[slot % len(holders)])
                    reservoir[slot] = (j, jax.block_until_ready(copy))
            del slots
        end = time.perf_counter() - t0
    records = {
        "rows": n, "cap": shards * cap, "columns": len(reference.SCAN_COLUMNS),
        "counts": [c for _, _, c in sent], "compiles_in_window": window.compiles,
        "bytes_exchanged": scan.exchange.bytes_exchanged - before[0],
        "consumer_plane": f"/device:TPU:{scan.consumer.id}",
    }
    say(window_s=end, requests=len(sent), compiles_in_window=window.compiles,
        bytes_exchanged=records["bytes_exchanged"], owner_overflows=scan.exchange.overflows - before[1],
        samples_copied=spans.count.get("scan.sample", 0), sample_copy_s=spans.total_s.get("scan.sample", 0.0),
        median_request_s=float(np.median(took)) if took else 0.0, longest_request_s=max(took, default=0.0))
    failed_requests = 0

    def verify() -> list[Check]:
        nonlocal failed_requests
        t = time.perf_counter()
        parts = owner_rows(state["scanned"].columns, shards)
        owner_counts = jax.device_get(counts)
        samples = list(filter(None, reservoir))
        held = {j: np.stack([cols[c] for c in reference.SCAN_COLUMNS], axis=1)
                for (j, _), cols in zip(samples, jax.device_get([cols for _, cols in samples]))}
        state.clear()
        counts.clear()
        reservoir.clear()
        fetched = time.perf_counter() - t
        with concurrent.futures.ThreadPoolExecutor(shards) as pool:
            refs = list(pool.map(reference.ScanReference, parts))
        out, failed_requests = checks(sent, owner_counts, held, refs, cap, keep)
        say(fetch_s=fetched, reference_s=time.perf_counter() - t - fetched, rows_checked_requests=sorted(held))
        held.clear()
        return out

    return Outcome(
        window_start=t0, e2e={"scan_rows_per_s": len(sent) * n / end}, attempted=len(sent), records=records,
        verify=verify, failed=lambda checks: failed_requests,
    )
