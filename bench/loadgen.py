"""Traffic from a workload file and a seed.

The TPC-H substitution-parameter ranges of Q1, Q6 and Q12 of the program's
load generator (``repro.runtime.loadgen.sample_params``), copied so that a
cell's traffic cannot move with the program.  Every seed gives the same mix
of queries and the same scan widths, so seeds change which constants and
which order, not how much work.

Workload keys (``bench/workloads/<traffic>.json``):

``queries``        serving: the query names served.
``clients``        serving: clients in a closed loop, each with one request
                   in flight; each walks the queries in a seeded order,
                   reshuffled every cycle, and sends the next on reply.
``selectivity``    scans: the share of ship dates a predicate window spans.
"""
from __future__ import annotations

import math
import random
from typing import Any, Iterator

from bench import datagen


def rng(seed: int, stream: str) -> random.Random:
    """An independent random stream per purpose, from any whole seed."""
    return random.Random(f"{seed}/{stream}")


def sample_params(query: str, r: random.Random) -> dict[str, Any]:
    """One request's constants, uniform over the spec's substitution ranges
    (TPC-H 2.4.1.3 Q1 DELTA, 2.4.6.3 Q6 DATE/DISCOUNT/QUANTITY, 2.4.12.3
    Q12 DATE), as the program's generator draws them."""
    if query == "q1":
        return {"delta_days": float(r.randint(60, 120))}
    if query == "q6":
        return {
            "year": r.randint(1993, 1997),
            "discount": round(r.uniform(0.02, 0.09), 2),
            "qty": float(r.randint(24, 25)),
        }
    if query == "q12":
        return {"year": r.randint(1993, 1997)}
    raise ValueError(f"unknown query {query!r}")


def client_requests(traffic: dict, seed: int, client: int) -> Iterator[tuple[str, dict]]:
    """The endless (query, constants) sequence of one closed-loop client."""
    qs = list(traffic["queries"])
    order, params = rng(seed, f"order/{client}"), rng(seed, f"params/{client}")
    while True:
        order.shuffle(qs)
        for q in qs:
            yield q, sample_params(q, params)


def scan_width_days(traffic: dict) -> float:
    return float(traffic["selectivity"]) * datagen.DATE_RANGE_DAYS


def scan_windows(traffic: dict, seed: int) -> Iterator[tuple[float, float]]:
    """Endless ``[lo, hi)`` ship-date windows: ``lo`` a whole day drawn
    uniformly so that the window lies in the data's date range; every
    window spans the same number of whole days."""
    width = scan_width_days(traffic)
    r = rng(seed, "scan")
    last = datagen.DATE_EPOCH_DAYS + datagen.DATE_RANGE_DAYS - math.ceil(width)
    while True:
        lo = float(r.randint(datagen.DATE_EPOCH_DAYS, last))
        yield lo, lo + width
