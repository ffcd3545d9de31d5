"""The exchange's share of its roofline: the bytes the consumer received
from the other owners in the window (the program's ``bytes_exchanged``
counter) at the consumer's inter-chip peak (``bench/ici.py``), over the
device time of the consumer's collective ops.  Nothing on a program
without the counter, or in a trace without collectives on the consumer."""
from bench import ici
from bench.harness import Reading
from bench.metrics import collectives


def read(r: Reading) -> float | None:
    if r.trace is None:
        return None
    sent = r.records.get("bytes_exchanged")
    ops = r.trace.ops.get(r.records.get("consumer_plane", ""), [])
    coll, _ = collectives.split(ops)
    seconds = collectives.length_ns(coll) * 1e-9
    if not sent or not seconds:
        return None
    return 100.0 * sent / ici.ici_bytes_per_s(r.device_kind) / seconds
