"""Requests served per kernel call in the window: completed requests over
the growth of ``QueryServer.kernel_calls``."""
from bench.harness import Reading


def read(r: Reading) -> float | None:
    calls = r.records.get("kernel_calls")
    return r.records["completed"] / calls if calls else None
