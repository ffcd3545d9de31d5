"""Streaming-compaction perf smoke: cap sweep across the old VMEM ceiling.

The resident ``block_compact`` keeps its whole padded ``[C, cap]`` output in
VMEM, so its capacity tops out at :data:`repro.kernels.ops.VMEM_BUDGET_BYTES`
(~512K rows at 4 columns).  The streaming variant keeps the output in HBM
and emits tiles by double-buffered DMA — capacity becomes memory-bounded.
This job pins that claim per commit:

  1. **Correctness** — at every swept cap (below the ceiling, above it, and
     one >= 4M rows) the streamed output is byte-diffed against the
     ``nonzero(size=cap)`` oracle, including a cap far below the mask count
     (overflow clamping at scale).
  2. **No small-cap regression** — the ``stream="auto"`` dispatcher must be
     no slower than the resident kernel at caps under the ceiling (it
     routes to it, so this catches dispatch overhead).  The raw streaming
     kernel also gets a sanity floor against resident: on the CPU
     interpreter the widened carry-merge scatter costs ~2x the resident
     store trick, so the floor only flags collapse, not interpreter skew —
     on TPU the DMA overlap is the whole point.
  3. **Trajectory** — BENCH_9.json records rows_per_s per (cap, impl).

Usage: python -m benchmarks.kernel_stream [--out BENCH_9.json] [--n ROWS]
       [--iters N]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.timing import block
from repro.kernels import ops as kops
from repro.kernels.ref import block_compact_ref

C = 4
SELECTIVITY = 0.5

#: Fraction of resident throughput the auto dispatcher must reach at caps
#: below the VMEM ceiling (same kernel underneath; slack covers CI timer
#: jitter, which reaches ~15% between identical interpret-mode runs).
AUTO_FLOOR = 0.75
#: Interpreter-only sanity floor for the raw streaming kernel (see module
#: docstring) — catches collapse, not the expected ~2x scatter overhead.
STREAM_FLOOR = 0.25


def default_caps(n: int) -> list[int]:
    """Caps straddling the resident kernel's VMEM ceiling, plus >= 4M."""
    ceiling = kops.VMEM_BUDGET_BYTES // (C * 4)  # rows where resident tops out
    return [ceiling // 8, ceiling // 2, 2 * ceiling, max(4 * 1024 * 1024, 8 * ceiling)]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmarks.kernel_stream")
    p.add_argument("--out", default="BENCH_9.json")
    p.add_argument("--n", type=int, default=1 << 21)
    p.add_argument("--iters", type=int, default=3)
    args = p.parse_args(argv)

    t0 = time.time()
    key = jax.random.PRNGKey(9)
    cols = jax.random.normal(key, (C, args.n), jnp.float32)
    mask = (
        jax.random.uniform(jax.random.fold_in(key, 1), (1, args.n)) < SELECTIVITY
    ).astype(jnp.int32)

    ceiling = kops.VMEM_BUDGET_BYTES // (C * 4)
    caps = default_caps(args.n)
    failures: list[str] = []
    entries: list[dict] = []
    rates: dict[tuple[int, str], float] = {}

    impls = (("resident", "never"), ("stream", "always"), ("auto", "auto"))
    for cap in caps:
        exp, ecnt = block_compact_ref(cols, mask, cap)
        fns = {
            impl: (lambda c, m, cap=cap, stream=stream:
                   kops.block_compact(c, m, cap, stream=stream))
            for impl, stream in impls
        }
        for impl, fn in fns.items():
            # Correctness byte-diff doubles as the compile warmup.
            out, cnt = fn(cols, mask)
            tag = f"cap={cap} impl={impl}"
            if int(cnt) != int(ecnt):
                failures.append(f"{tag}: count {int(cnt)} != oracle {int(ecnt)}")
            if not np.array_equal(np.asarray(out), np.asarray(exp)):
                bad = np.flatnonzero(
                    (np.asarray(out) != np.asarray(exp)).any(axis=0)
                )
                failures.append(f"{tag}: output differs at cols {bad[:8].tolist()}")
        # Interleave the timed iterations round-robin across impls: machine
        # drift (CI neighbors, thermal) then biases every impl equally
        # instead of landing wholesale on whichever ran last.
        times: dict[str, list[float]] = {impl: [] for impl in fns}
        for _ in range(max(1, args.iters)):
            for impl, fn in fns.items():
                ts = time.perf_counter()
                block(fn(cols, mask))
                times[impl].append(time.perf_counter() - ts)
        for impl in fns:
            rate = args.n / min(times[impl])
            rates[(cap, impl)] = rate
            entries.append(
                {"cap": cap, "impl": impl, "n": args.n,
                 "selectivity": SELECTIVITY, "rows_per_s": rate,
                 "above_vmem_ceiling": cap > ceiling}
            )
            print(f"# cap={cap} impl={impl}: {rate / 1e6:.1f}M rows/s "
                  f"({'above' if cap > ceiling else 'below'} ceiling)")

    for cap in caps:
        if cap > ceiling:
            continue
        auto_ratio = rates[(cap, "auto")] / rates[(cap, "resident")]
        if auto_ratio < AUTO_FLOOR:
            failures.append(
                f"cap={cap}: auto dispatch {auto_ratio:.2f}x of resident "
                f"(floor {AUTO_FLOOR})"
            )
        stream_ratio = rates[(cap, "stream")] / rates[(cap, "resident")]
        if stream_ratio < STREAM_FLOOR:
            failures.append(
                f"cap={cap}: raw stream collapsed to {stream_ratio:.2f}x of "
                f"resident (floor {STREAM_FLOOR})"
            )

    Path(args.out).write_text(
        json.dumps(
            {"bench": "kernel_stream", "vmem_ceiling_rows": ceiling,
             "auto_floor": AUTO_FLOOR, "stream_floor": STREAM_FLOOR,
             "failures": failures, "entries": entries},
            indent=1,
        )
        + "\n"
    )
    print(f"# wrote {args.out}: {len(entries)} entries in {time.time() - t0:.1f}s")
    for f in failures:
        print(f"FAIL {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
