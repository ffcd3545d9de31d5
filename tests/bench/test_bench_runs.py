"""Whole runs of every cell, on the CPU at a tiny scale with the kernels
interpreted: the program and the plain reference agree, and a run whose
timed path is broken underneath comes out not correct.  The look for a chip
is skipped (``bench.run.finish`` is the part of a run after it)."""
from __future__ import annotations

import dataclasses
import importlib
import io
import json
import subprocess
import sys
import types
from contextlib import redirect_stdout
from pathlib import Path

import jax
import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
MAN = run.manifest()
SCALE = 0.001  # 6,001 lineitem rows, 1,500 orders


def _cell(name: str, **traffic) -> run.harness.Cell:
    cell = run.load_cell(MAN, name)
    return dataclasses.replace(cell, config=dict(cell.config, scale=SCALE), traffic=dict(cell.traffic, **traffic))


def _run(cell, seed=2**33 + 7, seconds=1.0, trace=0) -> dict:
    driver = importlib.import_module(f"bench.drivers.{cell.config['driver']}")
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.finish(MAN, cell, jax.devices(), args, driver.run) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _assert_result(res: dict, cell: str, traced: bool):
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in run.reported(MAN, cell, traced)}
    if traced:  # the CPU has no device trace: only the host readers report
        assert set(res["metrics"]) <= want and "breakdown" in res
    else:
        assert set(res["metrics"]) == want
        assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("clients", [16, 3])
def test_serve_closed_loop_agrees_with_the_reference(clients, traced):
    res = _run(_cell("serve_closed_16", clients=clients), seconds=0.5, trace=int(traced))
    _assert_result(res, "serve_closed_16", traced)
    if traced:
        assert res["metrics"]["serve_tick_ms"]["value"] > 0
        assert res["metrics"]["serve_batch_mean"]["value"] >= 1


@pytest.mark.parametrize("cell", ["pushdown_sel0.5", "pushdown_sel0.001"])
def test_pushdown_agrees_with_the_reference(cell):
    res = _run(_cell(cell), seconds=0.5)
    _assert_result(res, cell, traced=False)


# -- faults planted under the timed path --------------------------------------
def _perturb(result: dict) -> dict:
    k = next(iter(result))
    return {**result, k: result[k] + 1.0}


def _serve_answer_altered(monkeypatch):
    from repro.engine import queries

    serial, batch = queries.fused_query_serial, queries.fused_query_batch
    monkeypatch.setattr(queries, "fused_query_serial", lambda *a, **k: _perturb(serial(*a, **k)))
    monkeypatch.setattr(queries, "fused_query_batch", lambda *a, **k: [_perturb(r) for r in batch(*a, **k)])


def _serve_half_batch(monkeypatch):
    """The batch's second half is left out: its slots repeat the first's."""
    from repro.engine import queries

    batch = queries.fused_query_batch

    def half(plan, params, **k):
        first = batch(plan, params[: max(len(params) // 2, 1)], **k)
        return [first[i % len(first)] for i in range(len(params))]

    monkeypatch.setattr(queries, "fused_query_batch", half)


def _serve_state_unchanged(monkeypatch):
    """Every request gets the first answer its query ever got."""
    from repro.engine import queries

    serial, batch, first = queries.fused_query_serial, queries.fused_query_batch, {}
    monkeypatch.setattr(queries, "fused_query_serial",
                        lambda plan, p, **k: first.setdefault(plan.name, serial(plan, p, **k)))
    monkeypatch.setattr(queries, "fused_query_batch",
                        lambda plan, ps, **k: [first.setdefault(plan.name, r) for r in batch(plan, ps, **k)])


@pytest.mark.parametrize("fault", [_serve_answer_altered, _serve_half_batch, _serve_state_unchanged])
def test_serving_faults_are_not_correct(monkeypatch, fault):
    """In the cell whose 16 clients keep batches of up to 8 in flight."""
    from repro.runtime.serve_query import QueryServer

    batches, execute = [], QueryServer._execute

    def counted(self, batch):
        if batch[0].uid >= 0:  # the window's batches; warm-up requests have uid -1
            batches.append(len(batch))
        return execute(self, batch)

    monkeypatch.setattr(QueryServer, "_execute", counted)
    fault(monkeypatch)
    res = _run(_cell("serve_closed_16"), seconds=0.5)
    assert res["correct"] is False and res["failed"] > 0, res["checks"]
    assert max(batches) == 8


def _scan_count_altered(monkeypatch, plan):
    monkeypatch.setattr(sys.modules["bench.drivers.pushdown"], "plan",
                        lambda cap: (lambda f: lambda t, lo, hi: (lambda o: (o[0], o[1] + 1))(f(t, lo, hi)))(plan(cap)))


def _scan_half_rows(monkeypatch, plan):
    """The compaction sees the table's first half only."""
    def halved(cap):
        f = plan(cap)
        return lambda t, lo, hi: f(t.slice_rows(0, t.num_rows // 2), lo, hi)

    monkeypatch.setattr(sys.modules["bench.drivers.pushdown"], "plan", halved)


def _scan_state_unchanged(monkeypatch, plan):
    """Every request gets the first request's answer."""
    def stale(cap):
        f, first = plan(cap), []
        return lambda t, lo, hi: first[0] if first else first.append(f(t, lo, hi)) or first[0]

    monkeypatch.setattr(sys.modules["bench.drivers.pushdown"], "plan", stale)


@pytest.mark.parametrize("fault", [_scan_count_altered, _scan_half_rows, _scan_state_unchanged])
def test_pushdown_faults_are_not_correct(monkeypatch, fault):
    from bench.drivers import pushdown

    fault(monkeypatch, pushdown.plan)
    res = _run(_cell("pushdown_sel0.5"), seconds=0.3)
    assert res["correct"] is False, res["checks"]


def test_refuses_to_run_without_a_tpu(tmp_path):
    cmd = MAN["command"] + ["--workload", "serve_closed_16", "--seed", "1", "--seconds", "1", "--trace", "0"]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)}
    for where in (ROOT, _only_the_benchmark(tmp_path)):
        p = subprocess.run([sys.executable, *cmd[1:]], cwd=where, env=env, capture_output=True, text=True,
                           timeout=120)
        assert p.returncode != 0
        assert "{" not in p.stdout


def _only_the_benchmark(tmp_path: Path) -> Path:
    import shutil

    dst = tmp_path / "checkout"
    dst.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, dst / p, ignore=shutil.ignore_patterns("__pycache__"))
    return dst

