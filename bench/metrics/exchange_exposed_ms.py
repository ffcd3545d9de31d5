"""Per request, the device milliseconds of the collective ops during which
no other op runs on that chip, averaged over the chips: the exchange's time
that compute does not hide."""
from bench.harness import Reading
from bench.metrics import collectives


def read(r: Reading) -> float | None:
    if r.trace is None or not r.trace.ops:
        return None
    requests = len(r.records.get("counts") or ())
    if not requests or not r.records.get("bytes_exchanged"):
        return None
    ns = sum(collectives.exposed_ns(*collectives.split(ops)) for ops in r.trace.ops.values())
    return 1e-6 * ns / len(r.trace.ops) / requests
