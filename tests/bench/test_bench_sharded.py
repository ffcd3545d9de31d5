"""Whole runs of the four-chip pushdown cell, on four virtual CPU devices at
a tiny scale (4 x 40,013 rows) with the kernels interpreted: the program and
the per-shard references agree, and a run with a fault planted under the
timed path comes out not correct.  Each run is a subprocess, since the test
process has one device:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python tests/bench/test_bench_sharded.py <fault>

prints the run's result as its last line.  The readers of the exchange's
metrics are checked on hand-made traces."""
from __future__ import annotations

import dataclasses
import io
import json
import os
import subprocess
import sys
import types
from contextlib import redirect_stdout
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "pushdown4_sel0.1"
SCALE = 0.02667  # 160,052 lineitem rows, four shards of 40,013
FAULTS = ("none", "owner_slot_zeroed", "stale_answer", "count_off_by_one")


# -- faults planted under the timed path (in the subprocess) -------------------
def _plant(fault: str) -> None:
    import jax

    from repro.engine import ops

    shard_compact = ops.shard_compact
    if fault == "owner_slot_zeroed":  # the consumer's slot of owner 1 reads zeros
        ops.shard_compact = lambda *a, **k: (
            lambda out: (jax.tree.map(lambda c: c.at[1].set(0), out[0]), out[1]))(shard_compact(*a, **k))
    elif fault == "count_off_by_one":  # owner 0's count, on the consumer
        ops.shard_compact = lambda *a, **k: (
            lambda out: (out[0], out[1].at[0].add(1)))(shard_compact(*a, **k))
    elif fault == "stale_answer":  # every request gets the first answer
        call, first = ops.ShardScan.__call__, []
        ops.ShardScan.__call__ = lambda self, *a: first[0] if first else first.append(call(self, *a)) or first[0]


def _run_cell(fault: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import run
    from bench.drivers import pushdown_sharded

    _plant(fault)
    man = run.manifest()
    cell = run.load_cell(man, CELL)
    cell = dataclasses.replace(cell, config=dict(cell.config, scale=SCALE))
    args = types.SimpleNamespace(seed=2**33 + 5, seconds=1.0, trace=0)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.finish(man, cell, jax.devices(), args, pushdown_sharded.run)
    print(out.getvalue().strip().splitlines()[-1])
    return rc


def _result(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, __file__, fault], cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_scan_agrees_with_the_reference():
    from bench import run

    res = _result("none")
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == 4
    want = {m["name"] for m in run.reported(run.manifest(), CELL, False)}
    assert set(res["metrics"]) == want == {"scan_rows_per_s", "setup_s"}
    assert set(res["checks"]) == {
        "requests_with_wrong_total", "requests_with_wrong_owner_count", "requests_with_overflow",
        "compacted_values_wrong", "requests_row_checked_missing"}


@pytest.mark.parametrize("fault", FAULTS[1:])
def test_sharded_faults_are_not_correct(fault):
    res = _result(fault)
    assert res["correct"] is False, res["checks"]
    if fault != "owner_slot_zeroed":  # the slots are checked on sampled requests only
        assert res["failed"] > 0


def test_overflow_fails_the_request():
    from bench import reference
    from bench.drivers.pushdown_sharded import checks

    ship = [8035.0, 8036.0, 8036.0, 8040.0]
    cols = {c: _f32(ship if c == "l_shipdate" else [1, 2, 3, 4]) for c in reference.SCAN_COLUMNS}
    refs = [reference.ScanReference(cols)]
    got, failed = checks([(8036.0, 8037.0, 2)], [[2]], {}, refs, cap=1, keep=0)
    by = {c.name: c.value for c in got}
    assert by["requests_with_overflow"] == 1 and failed == 1
    assert by["requests_with_wrong_total"] == by["requests_with_wrong_owner_count"] == 0


def test_owner_rows_gives_each_owner_its_rows_in_order():
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.drivers.pushdown_sharded import owner_rows

    mesh = Mesh(np.array(jax.devices()), ("x",))
    s = mesh.size
    col = jax.device_put(np.arange(8 * s, dtype=np.float32), NamedSharding(mesh, P("x")))
    parts = owner_rows({"a": col, "b": col + 1}, s)
    assert len(parts) == s
    for i, part in enumerate(parts):
        np.testing.assert_array_equal(part["a"], np.arange(8 * i, 8 * (i + 1), dtype=np.float32))
        np.testing.assert_array_equal(part["b"], part["a"] + 1)
    with pytest.raises(AssertionError):
        owner_rows({"a": col}, s + 1)


def _f32(values):
    import numpy as np

    return np.asarray(values, np.float32)


# -- the exchange's readers on hand-made traces --------------------------------
def _trace(ops_by_plane: dict[str, list[tuple[str, float, float]]]):
    from bench import trace

    ops = {p: [trace.Event(n, a, b - a) for n, a, b in evs] for p, evs in ops_by_plane.items()}
    return trace.Trace(ops=ops, spans=[], window=(0.0, 1e9))


def _reading(t, **records):
    from bench import harness, run

    cell = run.load_cell(run.manifest(), CELL)
    return harness.Reading(cell, "TPU v5 lite", records, None, t)


CONSUMER = [  # two requests; in each, three rounds in flight together
    ("%fusion.1 = f32[4] fusion()", 0, 400e3),
    ("%collective-permute-start.1 = (f32[8]) collective-permute-start()", 400e3, 401e3),
    ("%collective-permute-start.2 = (f32[8]) collective-permute-start()", 401e3, 402e3),
    ("%copy.3 = f32[8] copy()", 402e3, 500e3),
    ("%collective-permute-done.1 = f32[8] collective-permute-done()", 500e3, 600e3),
    ("%collective-permute-done.2 = f32[8] collective-permute-done()", 600e3, 700e3),
    ("%all-gather = s32[4] all-gather()", 800e3, 900e3),
    ("%fusion.2 = f32[4] fusion()", 2e6, 3e6),
]
OWNER = [("%fusion.1 = f32[4] fusion()", 0, 500e3),
         ("%collective-permute-start.1 = (f32[8]) collective-permute-start()", 500e3, 501e3),
         ("%collective-permute-done.1 = f32[8] collective-permute-done()", 501e3, 700e3)]


def test_collectives_pair_start_and_done_and_split_exposed_time():
    from bench.metrics import collectives

    coll, other = collectives.split([_trace({"p": CONSUMER}).ops["p"]][0])
    assert collectives.union(coll) == [(400e3, 700e3), (800e3, 900e3)]
    assert collectives.length_ns(coll) == 400e3
    assert collectives.exposed_ns(coll, other) == 400e3 - 98e3  # the copy hides 98 us


def test_exchange_readers():
    from bench.metrics import exchange_exposed_ms, exchange_ici_pct

    t = _trace({"/device:TPU:0": CONSUMER, "/device:TPU:1": OWNER})
    r = _reading(t, bytes_exchanged=20_000_000, consumer_plane="/device:TPU:0", counts=[5, 7])
    # 20 MB at 200 GB/s is 100 us, over 400 us of collectives on the consumer
    assert exchange_ici_pct.read(r) == pytest.approx(25.0)
    # exposed: 302 us on the consumer, 200 us on the owner; per request, over two chips
    assert exchange_exposed_ms.read(r) == pytest.approx((302e3 + 200e3) * 1e-6 / 2 / 2)


def test_exchange_readers_read_nothing_without_the_program_counter():
    from bench.metrics import exchange_exposed_ms, exchange_ici_pct

    t = _trace({"/device:TPU:0": CONSUMER})
    for records in ({"counts": [5]}, {}):
        r = _reading(t, **records)
        assert exchange_ici_pct.read(r) is None and exchange_exposed_ms.read(r) is None
    assert exchange_ici_pct.read(_reading(None, bytes_exchanged=1, counts=[1])) is None


if __name__ == "__main__":
    raise SystemExit(_run_cell(sys.argv[1]))
