"""Record the small device trace that tests/bench keeps as a fixture.

    python3 bench/record_fixture.py --out chiprun_out/fixture

On one TPU chip: three query-server ticks (q6 at batch 1 and 2) and one
resident and one streaming compaction over a 1,048,576-row lineitem, each
inside the benchmark's host spans, traced in one window.  Writes the
``.xplane.pb`` and a text listing of its planes, lines and first events.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

ROWS = 1 << 20


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", required=True)
    args = p.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    if jax.devices()[0].platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        return 2
    from bench import datagen, trace
    from repro.engine import ops, queries
    from repro.engine.table import Table
    from repro.runtime.requests import QueryRequest
    from repro.runtime.serve_query import QueryServer

    li_cols, od_cols = datagen.tables(1, ROWS, ROWS // 4)
    li = Table(li_cols)
    plans = queries.make_serving_plans(li, Table(od_cols))
    server = QueryServer({"q6": plans["q6"]}, max_batch=2)
    scanned = li.select("l_shipdate", "l_extendedprice", "l_discount", "l_quantity")

    def scan(t, lo, hi, cap):
        return ops.compact(t, ops.pred_between(t["l_shipdate"], lo, hi), cap, use_pallas=True)

    scans = {cap: jax.jit(lambda t, lo, hi, cap=cap: scan(t, lo, hi, cap)) for cap in (4096, 600_000)}
    params = [{"year": 1994, "discount": 0.06, "qty": 24.0}, {"year": 1995, "discount": 0.05, "qty": 25.0}]
    spans = trace.Spans()

    def work():
        for batch in ([params[0]], params, [params[1]]):
            for i, prm in enumerate(batch):
                server.submit(QueryRequest(uid=i, query="q6", params=prm))
            with spans("serve.step"):
                done = server.step()
            with spans("serve.fetch"):
                jax.device_get([c.result for c in done])
        for cap, width in ((4096, 2.0), (600_000, 1263.0)):
            with spans("scan.request"):
                out, cnt = scans[cap](scanned, jnp.float32(9000.0), jnp.float32(9000.0 + width))
            with spans("scan.fetch_count"):
                int(cnt)

    work()  # compile and warm every shape outside the trace
    tmp = tempfile.mkdtemp()
    try:
        trace.start(tmp)
        with spans(trace.WINDOW):
            work()
        jax.profiler.stop_trace()
        os.makedirs(args.out, exist_ok=True)
        dst = os.path.join(args.out, "trace.xplane.pb")
        shutil.copy(trace.find_xplane(tmp), dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    lines = []
    for plane in ProfileData.from_file(dst).planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:40]:
                lines.append(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns}")
    Path(args.out, "structure.txt").write_text("\n".join(lines) + "\n")
    t = trace.load(dst)
    print({"busy_s": t.busy_s(), "window_s": t.window_s, **trace.breakdown(t)})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
