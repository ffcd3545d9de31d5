"""Query-serving front end: seeded open-loop load generation, admission
control, scan-sharing micro-batches (equal to serial execution: counts
exactly, float sums within a few ulps), and the unified executor-config
surface."""
from __future__ import annotations

import argparse
import random

import jax
import numpy as np
import pytest

from repro.core import config as config_mod
from repro.core.metrics import Samples, compute_metrics
from repro.engine import datagen, queries
from repro.kernels.group_filter_agg import encode_aggregates, encode_predicates
from repro.runtime.loadgen import arrival_times, generate_trace, sample_params
from repro.runtime.requests import QueryRequest, RequestQueue
from repro.runtime.serve_query import (
    QueryServer,
    measure_saturation,
    run_open_loop,
)

ROWS = 2_000
# Serial and batched requests run one kernel and visit the data blocks in
# the same order, but interpret mode on the CPU lets XLA reorder the
# additions inside a block's dot, so float sums may differ by a few ulps.
FLOAT_RTOL = 1e-6


def _assert_same_result(want, got, label):
    assert set(want) == set(got), label
    for k in want:
        w, g = np.asarray(want[k]), np.asarray(got[k])
        if k in queries.COUNT_KEYS:
            assert np.array_equal(w, g), (label, k)
        else:
            np.testing.assert_allclose(g, w, rtol=FLOAT_RTOL, atol=0, err_msg=f"{label} {k}")


@pytest.fixture(scope="module")
def tables():
    li = datagen.lineitem(jax.random.PRNGKey(0), rows=ROWS)
    od = datagen.orders(jax.random.PRNGKey(1), rows=ROWS // 4)
    return li, od


@pytest.fixture(scope="module")
def plans(tables):
    return queries.make_serving_plans(*tables)


# -- open-loop load generation -------------------------------------------------
def test_poisson_arrivals_reproducible():
    a = arrival_times(200.0, 1.0, arrival="poisson", seed=7)
    b = arrival_times(200.0, 1.0, arrival="poisson", seed=7)
    assert a == b
    assert a != arrival_times(200.0, 1.0, arrival="poisson", seed=8)
    assert all(0.0 <= t < 1.0 for t in a)
    assert a == sorted(a)
    # Poisson(200/s) over 1s: far from degenerate on either side.
    assert 100 < len(a) < 400


def test_fixed_arrivals_exact():
    assert arrival_times(10.0, 1.0, arrival="fixed") == [i / 10.0 for i in range(10)]


def test_trace_deterministic_and_round_robin():
    t1 = generate_trace(["q1", "q6"], 100.0, 0.5, arrival="poisson", seed=3)
    t2 = generate_trace(["q1", "q6"], 100.0, 0.5, arrival="poisson", seed=3)
    assert [(r.uid, r.query, r.params, r.arrival_s) for r in t1] == [
        (r.uid, r.query, r.params, r.arrival_s) for r in t2
    ]
    assert [r.query for r in t1[:4]] == ["q1", "q6", "q1", "q6"]
    # a different seed moves both arrivals and constants
    t3 = generate_trace(["q1", "q6"], 100.0, 0.5, arrival="poisson", seed=4)
    assert [r.params for r in t1] != [r.params for r in t3]


def test_sample_params_in_kernel_domain():
    rng = random.Random(0)
    for _ in range(50):
        p = sample_params("q6", rng)
        assert 1993 <= p["year"] <= 1997
        assert 0.02 <= p["discount"] <= 0.09
    with pytest.raises(ValueError):
        sample_params("q99", rng)


# -- admission control ---------------------------------------------------------
def test_request_queue_sheds_exactly_overflow():
    q = RequestQueue(depth=4)
    admitted = [q.submit(i) for i in range(7)]
    assert admitted == [True] * 4 + [False] * 3
    assert (q.offered, q.admitted, q.shed) == (7, 4, 3)
    assert [q.popleft() for _ in range(len(q))] == [0, 1, 2, 3]  # FIFO
    # draining frees capacity again
    assert q.submit(99) is True
    assert (q.offered, q.admitted, q.shed) == (8, 5, 3)


def test_request_queue_take_matching_preserves_order():
    q = RequestQueue()
    for i, name in enumerate(["a", "b", "a", "a", "b", "a"]):
        q.submit((i, name))
    taken = q.take_matching(lambda r: r[1] == "a", limit=3)
    assert [i for i, _ in taken] == [0, 2, 3]
    assert list(q) == [(1, "b"), (4, "b"), (5, "a")]  # untouched order


def test_request_queue_thread_safe_under_hammer():
    """Regression: submit/take_matching raced before the internal lock.

    8 submitter threads push disjoint uid ranges while 4 drainers spin
    take_matching; afterwards every admitted request must have been taken
    exactly once and the counters must satisfy offered == admitted + shed.
    """
    import threading

    q = RequestQueue(depth=64)
    n_submitters, per_thread = 8, 500
    taken: list = []
    taken_lock = threading.Lock()
    done = threading.Event()

    def submitter(base):
        for i in range(per_thread):
            q.submit((base + i, "a" if i % 2 else "b"))

    def drainer():
        while not done.is_set() or len(q):
            got = q.take_matching(lambda r: True, limit=7)
            if got:
                with taken_lock:
                    taken.extend(got)

    drainers = [threading.Thread(target=drainer) for _ in range(4)]
    for t in drainers:
        t.start()
    submitters = [
        threading.Thread(target=submitter, args=(k * per_thread,))
        for k in range(n_submitters)
    ]
    for t in submitters:
        t.start()
    for t in submitters:
        t.join()
    done.set()
    for t in drainers:
        t.join()

    assert q.offered == n_submitters * per_thread
    assert q.offered == q.admitted + q.shed  # the invariant the lock protects
    assert len(taken) == q.admitted  # nothing lost, nothing duplicated
    assert len({uid for uid, _ in taken}) == len(taken)


def test_server_sheds_at_oversaturation(plans):
    server = QueryServer(plans, queue_depth=2, max_batch=4)
    reqs = [
        QueryRequest(uid=i, query="q6", params=sample_params("q6", random.Random(i)))
        for i in range(6)
    ]
    results = [server.submit(r) for r in reqs]
    assert results == [True, True, False, False, False, False]
    assert server.queue.shed == 4
    done = server.step()
    assert {c.uid for c in done} == {0, 1}
    assert done[0].batch_size == 2


# -- scan sharing: equal to serial ---------------------------------------------
@pytest.mark.parametrize("qname", ["q1", "q6", "q12"])
@pytest.mark.parametrize("use_pallas", [True, False])
def test_micro_batch_byte_equals_serial(plans, qname, use_pallas):
    rng = random.Random(11)
    param_list = [sample_params(qname, rng) for _ in range(5)]
    batched = queries.fused_query_batch(plans[qname], param_list, use_pallas=use_pallas)
    for params, got in zip(param_list, batched):
        want = queries.fused_query_serial(plans[qname], params, use_pallas=use_pallas)
        _assert_same_result(want, got, qname)


def _fused(qname, tables, params):
    """The ``q*_fused`` device path: the demux runs on device arrays."""
    li, od = tables
    args = (li, od) if qname == "q12" else (li,)
    return queries.FUSED_QUERIES[qname](*args, **params)


@pytest.mark.parametrize("qname", sorted(queries.SPECS))
def test_every_spec_is_fused_and_served_alike(tables, plans, qname):
    """Each spec gives one fused query and one serving plan, and the two
    answer alike: counts exactly, float sums within a few ulps."""
    spec = queries.SPECS[qname]
    assert set(queries.FUSED_QUERIES) == set(plans) == set(queries.SPECS)
    assert queries.FUSED_QUERIES[qname].__name__ == f"{qname}_fused"
    plan = plans[qname]
    assert plan.num_groups == spec.num_groups and plan.demux is spec.demux
    params = sample_params(qname, random.Random(17))
    (got,) = queries.fused_query_batch(plan, [params])
    _assert_same_result(_fused(qname, tables, params), got, qname)


def _is_host_value(x) -> bool:
    return isinstance(x, (np.ndarray, np.generic))


@pytest.mark.parametrize("qname", ["q1", "q6", "q12"])
@pytest.mark.parametrize("size", [1, 2, 3, 8])
def test_batch_equals_the_fused_device_path(tables, plans, qname, size):
    """The host demux of a batch gives the device demux's answers; 3
    requests are padded to 4 slots as the server pads them."""
    rng = random.Random(size)
    param_list = [sample_params(qname, rng) for _ in range(size)]
    slots = 1 << (size - 1).bit_length()
    padded = param_list + [param_list[0]] * (slots - size)
    got = queries.fused_query_batch(plans[qname], padded)[:size]
    for params, result in zip(param_list, got, strict=True):
        _assert_same_result(_fused(qname, tables, params), result, (qname, params))
        assert all(_is_host_value(v) for v in result.values()), result


def test_serving_results_are_host_values(plans):
    rng = random.Random(2)
    server = QueryServer(plans, max_batch=8)
    for i, name in enumerate(["q1", "q1", "q6", "q12"]):
        server.submit(QueryRequest(uid=i, query=name, params=sample_params(name, rng)))
    done = []
    while len(server.queue):
        done += server.step()
    assert sorted(c.uid for c in done) == [0, 1, 2, 3]
    for c in done:
        assert all(_is_host_value(v) for v in c.result.values()), (c.query, c.result)


def test_constant_tables_are_built_on_the_host():
    pred = encode_predicates([("range", 0, 1.0, 2.0), ("lt", 1, 2)])
    agg = encode_aggregates([[("col", 1), ("le", 2, 0.5)]])
    for table in (*pred, *agg, *queries.q1_program(), *queries.q12_program(1995)):
        assert isinstance(table, np.ndarray), type(table)


def test_plan_opcode_tables_stay_on_the_device(plans):
    for plan in plans.values():
        assert isinstance(plan.pred_ops, jax.Array) and isinstance(plan.agg_ops, jax.Array)
        consts = plan.program(sample_params(plan.name, random.Random(0)))
        assert all(isinstance(c, np.ndarray) for c in consts)


@pytest.mark.parametrize("qname", ["q1", "q6", "q12"])
def test_a_warm_tick_copies_only_explicitly(plans, qname):
    """A warm tick of three requests makes one explicit copy of the
    constant tables to the device and one of the output back: under a
    guard that refuses implicit host-device copies it still serves."""
    server = QueryServer(plans, max_batch=8)
    rng = random.Random(4)

    def tick():
        for i in range(3):
            server.submit(QueryRequest(uid=i, query=qname, params=sample_params(qname, rng)))
        return server.step()

    tick()  # compiles the 4-slot program
    with jax.transfer_guard("disallow"):
        done = tick()
    assert [c.uid for c in done] == [0, 1, 2]
    assert server.kernel_calls == 2


def test_server_batched_results_byte_equal_serial(plans):
    """End to end through the scheduler tick: coalesced completions carry
    the results serial per-request execution would have produced."""
    rng = random.Random(5)
    reqs = [
        QueryRequest(uid=i, query="q6", params=sample_params("q6", rng)) for i in range(7)
    ]
    server = QueryServer(plans, max_batch=8)
    for r in reqs:
        server.submit(r)
    done = server.step()
    assert len(done) == 7 and all(c.batch_size == 7 for c in done)
    for req, c in zip(reqs, done):
        assert c.uid == req.uid
        want = queries.fused_query_serial(plans["q6"], req.params)
        _assert_same_result(want, c.result, req.uid)
    assert server.kernel_calls == 1  # one HBM pass for all seven requests


def test_a_one_request_tick_is_a_one_slot_batch(plans, monkeypatch):
    """A lone request takes the batch path, padded to one slot, like any
    other tick."""
    from repro.core import tracing

    calls = []
    batch = queries.fused_query_batch

    def counted(plan, param_list, **kw):
        calls.append(len(param_list))
        return batch(plan, param_list, **kw)

    monkeypatch.setattr(queries, "fused_query_batch", counted)
    monkeypatch.setattr(queries, "fused_query_serial", None)  # no second launch path
    server = QueryServer(plans, max_batch=8)
    req = QueryRequest(uid=0, query="q1", params=sample_params("q1", random.Random(3)))
    server.submit(req)
    (done,) = server.step()
    assert calls == [1] and server.kernel_calls == 1
    assert tracing.RECORDER.ticks[-1].slots == 1
    assert done.uid == 0 and done.batch_size == 1
    _assert_same_result(batch(plans["q1"], [req.params])[0], done.result, "q1")


@pytest.mark.parametrize("size", [8, 1])
def test_a_warm_q1_tick_takes_one_mxu_pass(plans, size):
    """Eight q1 requests share one one-hot pass a block, as one does."""
    from repro.core import tracing

    server = QueryServer(plans, max_batch=8)
    server.warmup(["q1"])
    rng = random.Random(size)
    for i in range(size):
        server.submit(QueryRequest(uid=i, query="q1", params=sample_params("q1", rng)))
    assert len(server.step()) == size
    tick = tracing.RECORDER.ticks[-1]
    assert (tick.slots, tick.passes) == (size, 1)


def test_server_coalesces_only_same_query_shape(plans):
    server = QueryServer(plans, max_batch=8)
    rng = random.Random(0)
    for i, name in enumerate(["q6", "q1", "q6"]):
        server.submit(QueryRequest(uid=i, query=name, params=sample_params(name, rng)))
    first = server.step()
    assert [c.uid for c in first] == [0, 2]  # both q6s, one pass
    second = server.step()
    assert [c.uid for c in second] == [1]
    assert server.kernel_calls == 2


# -- percentile math -----------------------------------------------------------
def test_p50_p99_match_numpy_percentile():
    lat = [0.004, 0.001, 0.010, 0.002, 0.007, 0.003, 0.009, 0.005]
    s = Samples(times_s=list(lat))
    got = compute_metrics(s, ("p50_latency_us", "p99_latency_us"))
    assert got["p50_latency_us"] == pytest.approx(1e6 * float(np.percentile(lat, 50)))
    assert got["p99_latency_us"] == pytest.approx(1e6 * float(np.percentile(lat, 99)))


# -- open-loop serving runs ----------------------------------------------------
def test_open_loop_run_below_saturation_sheds_nothing(plans):
    server = QueryServer(plans, queue_depth=32, max_batch=8)
    server.warmup(["q6"])
    trace = generate_trace(["q6"], 40.0, 0.4, arrival="fixed", seed=0)
    report = run_open_loop(server, trace)
    assert report.offered == len(trace)
    assert report.shed == 0
    assert len(report.completed) == len(trace)
    assert sorted(c.uid for c in report.completed) == [r.uid for r in trace]
    assert all(c.latency_s >= 0 for c in report.completed)
    assert report.qps > 0


def test_measure_saturation_positive(plans):
    qps = measure_saturation(plans, ["q6"], max_batch=4, n_requests=8)
    assert qps > 0


# -- serving task through the framework ----------------------------------------
def test_serving_task_reports_latency_and_saturation():
    from repro.core.registry import get
    from repro.core.task import TaskContext

    task = get("serving")
    ctx = TaskContext(platform={"name": "cpu-host"})
    task.prepare(ctx)
    s = task.run(
        ctx,
        {"scale": "0.001", "query": "q6", "rate": 30.0, "arrival": "fixed",
         "batching": True, "duration": 0.3, "queue_depth": 64, "seed": 0},
    )
    vals = compute_metrics(
        s, ("p50_latency_us", "p99_latency_us", "qps", "saturation_qps", "shed_requests")
    )
    assert vals["p50_latency_us"] > 0
    assert vals["p99_latency_us"] >= vals["p50_latency_us"]
    assert vals["saturation_qps"] > 0
    assert vals["shed_requests"] == 0
    assert len(s.times_s) == int(vals["completed_requests"])
    task.clean(ctx)


def test_serving_task_dilates_rates_on_simulated_platform():
    from repro.core.platform import get_platform
    from repro.core.registry import get
    from repro.core.task import TaskContext

    task = get("serving")
    ctx = TaskContext(platform={"name": "dpu-sim"})
    task.prepare(ctx)
    params = {"scale": "0.001", "query": "q6", "rate": 30.0, "arrival": "fixed",
              "batching": False, "duration": 0.2, "queue_depth": 0, "seed": 0}
    s = task.run(ctx, params)
    ts = get_platform("dpu-sim").time_scale
    assert ts > 1
    # rates were pre-divided: offered load 30/s reads as 30/ts on the sim
    assert s.extra["offered_qps"] == pytest.approx(30.0 / ts, rel=0.25)
    task.clean(ctx)


# -- unified executor-config API -----------------------------------------------
def test_sweep_config_round_trip_and_executor_mapping(tmp_path):
    p = argparse.ArgumentParser()
    config_mod.add_sweep_args(p)
    ns = p.parse_args(
        ["--iters", "7", "--warmup", "3", "--workers", "4", "--pool", "process",
         "--platforms", "cpu-host", "dpu-sim", "--schedule", "static",
         "--straggler-factor", "2.5", "--min-time", "0.1",
         "--cache", str(tmp_path / "c.json"), "--weighted-shard"]
    )
    cfg = config_mod.SweepConfig.from_args(ns)
    assert cfg.iters == 7 and cfg.warmup == 3 and cfg.workers == 4
    assert cfg.platforms == ["cpu-host", "dpu-sim"]
    ex = config_mod.make_executor(cfg)
    assert ex.iters == 7 and ex.warmup == 3 and ex.workers == 4
    assert ex.pool == "process" and ex.schedule == "static"
    assert ex.straggler_factor == 2.5 and ex.min_time_s == pytest.approx(0.1)
    assert ex.weighted_shard is True
    assert [pl.name for pl in ex.platforms] == ["cpu-host", "dpu-sim"]
    assert ex.cache is not None


def test_cache_file_is_alias_of_cache(tmp_path):
    p = argparse.ArgumentParser()
    config_mod.add_sweep_args(p)
    ns = p.parse_args(["--cache-file", str(tmp_path / "c.json")])
    assert ns.cache_path == str(tmp_path / "c.json")
    ns2 = p.parse_args(["--cache", str(tmp_path / "c.json")])
    assert ns2.cache_path == ns.cache_path


def test_no_cache_wins(tmp_path):
    cfg = config_mod.SweepConfig(cache_path=str(tmp_path / "c.json"), no_cache=True)
    assert config_mod.make_cache(cfg) is None
    assert config_mod.make_cache(config_mod.SweepConfig()) is None  # no path at all
    assert config_mod.make_cache(
        config_mod.SweepConfig(), default_path=tmp_path / "d.json"
    ) is not None


def test_cli_surfaces_share_sweep_flags():
    """The three entry points expose identical sweep flag sets (no drift)."""
    import benchmarks.run as bench_run
    from repro.core import runner as runner_mod
    from repro.runtime import serve_query

    def sweep_flags(build_parser):
        p = argparse.ArgumentParser()
        build_parser(p)
        return {
            s for a in p._actions for s in a.option_strings
        }

    base = sweep_flags(config_mod.add_sweep_args)
    assert "--cache" in base and "--cache-file" in base and "--shard" in base
    # Each CLI parses a sweep-only command line identically.
    for main in (runner_mod.main, bench_run.main, serve_query.main):
        with pytest.raises(SystemExit) as e:
            main(["--bogus-flag-that-cannot-exist"])
        assert e.value.code == 2
    # And accepts the shared flags without argparse errors (--list-style
    # early exits keep the parse cheap).
    assert runner_mod.main(["--list-tasks"]) == 0
    assert bench_run.main(["--list", "--workers", "3", "--shard", "0/2"]) == 0


def test_serving_box_runs_through_runner():
    from repro.core.box import Box
    from repro.core.runner import Runner

    box = Box.from_dict(
        {
            "name": "serving_smoke_box",
            "tasks": [
                {
                    "task": "serving",
                    "params": {"scale": "0.001", "query": ["q6"], "rate": 30.0,
                               "arrival": "fixed", "batching": True,
                               "duration": 0.2, "queue_depth": 32, "seed": 0},
                    "metrics": ["p50_latency_us", "p99_latency_us", "qps",
                                "saturation_qps", "shed_requests"],
                }
            ],
        }
    )
    res = Runner(platform="cpu-host", iters=1, warmup=0).run_box(box)
    assert not res.errors
    assert len(res.rows) == 1
    row = res.rows[0]
    assert row["p99_latency_us"] >= row["p50_latency_us"] > 0
    assert row["saturation_qps"] > 0
    assert row["shed_requests"] == 0


def test_serve_cli_smoke(tmp_path, capsys):
    from repro.runtime import serve_query

    out = tmp_path / "serve.csv"
    rc = serve_query.main(
        ["--query", "q6", "--arrival-rate", "30", "--duration", "0.2",
         "--arrival", "fixed", "--platforms", "cpu-host", "--out", str(out)]
    )
    assert rc == 0
    text = out.read_text()
    assert "p50_latency_us" in text and "saturation_qps" in text
