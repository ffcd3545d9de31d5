"""TPC-H query serving through the program's query server.

Set-up generates lineitem and orders on the device from the seed, builds the
program's serving plans (``engine.queries.make_serving_plans``) and a
``QueryServer`` with the configuration's ``max_batch`` and an unbounded
queue, and warms every padded batch size the traffic can form by serving
requests through ``submit``/``step``.  The window then drives the same
server in a closed loop: each client sends its next query when its reply is
on the host, until ``seconds`` have passed; the requests then in flight
finish, and the window closes when the last is done.

Afterwards the results of every request are compared with
:class:`bench.reference.ServingReference` by :func:`checks`.
"""
from __future__ import annotations

import time

import jax

from bench import datagen, loadgen, reference
from bench.harness import Cell, Check, Outcome, Window, say
from bench.trace import Spans


def _pow2_at_least(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def prepare(cell: Cell, seed: int) -> dict:
    """Set-up: tables, plans, the server, and every batch shape warmed."""
    from repro.engine import queries
    from repro.engine.table import Table
    from repro.runtime.requests import QueryRequest
    from repro.runtime.serve_query import QueryServer

    cfg, traffic = cell.config, cell.traffic
    state: dict = {}
    t = time.perf_counter()
    n, num_orders = datagen.rows(cfg["scale"])
    state["li"], state["od"] = jax.block_until_ready(datagen.tables(seed, n, num_orders))
    say(setup="datagen", seconds=time.perf_counter() - t, lineitem_rows=n, orders_rows=num_orders)

    t = time.perf_counter()
    plans = queries.make_serving_plans(Table(state["li"]), Table(state["od"]))
    plans = {q: plans[q] for q in traffic["queries"]}
    jax.block_until_ready([(p.cols, p.keys) for p in plans.values()])
    server = QueryServer(plans, queue_depth=None, max_batch=cfg["max_batch"])
    state["server"] = server
    say(setup="plans", seconds=time.perf_counter() - t)

    # Warm every padded batch size this traffic can form, through the
    # window's own calls: k clients never batch past k.
    t = time.perf_counter()
    widest = min(cfg["max_batch"], traffic["clients"])
    sizes = sorted({_pow2_at_least(b) for b in range(1, widest + 1)})
    warm = loadgen.rng(seed, "warmup")
    for q in traffic["queries"]:
        for b in sizes:
            for _ in range(b):
                server.submit(QueryRequest(uid=-1, query=q, params=loadgen.sample_params(q, warm)))
            jax.device_get([c.result for c in server.step()])
    server.completed.clear()
    say(setup="warmup", seconds=time.perf_counter() - t, batch_sizes=sizes)
    state["rows"] = n
    return state


def run(cell: Cell, seed: int, seconds: float, window: Window, spans: Spans) -> Outcome:
    return drive(prepare(cell, seed), cell.config, cell.traffic, seed, seconds, window, spans)


def checks(sent: list[tuple[str, dict]], results: dict[int, dict], ref, limits: dict) -> list[Check]:
    """The serving checks: ``sent[uid]`` is a request's (query, constants)
    and ``results[uid]`` its answer, compared with ``ref(query, constants)``."""
    wrong, worst = 0, 0.0
    for uid, (q, p) in enumerate(sent):
        if uid in results:
            exact, dev = reference.compare(results[uid], ref(q, p))
            wrong += not exact
            worst = max(worst, dev)
    return [
        Check("requests_unanswered", len(sent) - len(results), 0),
        Check("requests_with_wrong_counts", wrong, 0),
        Check("max_rel_dev", worst, limits["max_rel_dev"]),
    ]


def drive(state: dict, cfg: dict, traffic: dict, seed: int, seconds: float, window: Window,
          spans: Spans) -> Outcome:
    """The measured window over a prepared server; ``verify`` then frees
    ``state`` and checks every answer."""
    from repro.runtime.requests import QueryRequest

    server, n = state["server"], state["rows"]
    calls0 = server.kernel_calls
    clients = [loadgen.client_requests(traffic, seed, c) for c in range(traffic["clients"])]
    sent: list[tuple[str, dict]] = []  # uid -> (query, constants)
    client_of: dict[int, int] = {}
    results: dict[int, dict] = {}
    batches: list[tuple[str, int]] = []
    ticks: list[float] = []
    t0 = time.perf_counter()

    def send(client: int) -> None:
        q, p = next(clients[client])
        uid = len(sent)
        sent.append((q, p))
        client_of[uid] = client
        server.submit(QueryRequest(uid=uid, query=q, params=p, arrival_s=time.perf_counter() - t0))

    with window():
        t0 = time.perf_counter()
        for c in range(len(clients)):
            send(c)
        while len(server.queue):
            t = time.perf_counter()
            with spans("serve.step"):
                done = server.step()
            with spans("serve.fetch"):
                host = jax.device_get([c.result for c in done])
            ticks.append(time.perf_counter() - t)
            for c, r in zip(done, host):
                results[c.uid] = r
            batches.append((done[0].query, len(done)))
            if time.perf_counter() - t0 < seconds:
                for c in done:
                    send(client_of[c.uid])
        end = time.perf_counter() - t0

    records = {
        "rows": n, "completed": len(results), "kernel_calls": server.kernel_calls - calls0,
        "batches": batches, "compiles_in_window": window.compiles,
    }
    del server
    say(window_s=end, requests=len(sent), completed=len(results), kernel_calls=records["kernel_calls"],
        compiles_in_window=window.compiles, longest_tick_s=max(ticks, default=0.0),
        ticks_over_100ms=sum(t > 0.1 for t in ticks))

    def verify() -> list[Check]:
        host_li, host_od = jax.device_get((state["li"], state["od"]))
        state.clear()  # the program's tables, plans and server go here
        t = time.perf_counter()
        ref = reference.ServingReference(host_li, host_od)
        del host_li, host_od
        out = checks(sent, results, ref, cfg["limits"])
        say(reference_s=time.perf_counter() - t, compared=len(results))
        return out

    def failed(checks: list[Check]) -> int:
        by = {c.name: c.value for c in checks}
        return int(by["requests_unanswered"] + by["requests_with_wrong_counts"])

    return Outcome(
        window_start=t0, e2e={"query_per_s": len(results) / end}, attempted=len(sent), records=records,
        verify=verify, failed=failed,
    )
