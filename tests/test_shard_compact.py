"""``engine.ops.shard_compact`` against a plain numpy reference of the same
semantics, on four virtual CPU devices with the kernels interpreted, and on
a one-device mesh, where it gives what ``compact`` gives.

The four-device cases run in one subprocess, since the test process has one
device:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python tests/test_shard_compact.py

prints one JSON object of every case's per-owner counts, the reference's,
the cells of the consumer's slots that differ from the reference, and the
scan's exchange counters."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.engine import ops
from repro.engine.table import Table

ROWS_PER_OWNER = 40_000
LO, HI = 8100.0, 8352.6  # a tenth of the ship dates: about 4,000 rows an owner
#: name -> (use_pallas, per-owner capacity): 6,000 keeps block_compact in
#: VMEM, 600,000 streams it to HBM; 1,000 is too small for a tenth.
CASES = {
    "jnp-resident": (False, 6_000),
    "jnp-streaming": (False, 600_000),
    "kernel-resident": (True, 6_000),
    "kernel-streaming": (True, 600_000),
    "kernel-overflow": (True, 1_000),
}


def _columns(n: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(11)
    return {
        "l_discount": rng.integers(0, 11, n).astype(np.float32) / 100,
        "l_extendedprice": rng.uniform(900, 105_000, n).astype(np.float32),
        "l_quantity": rng.integers(1, 51, n).astype(np.float32),
        "l_shipdate": rng.integers(8035, 8035 + 2526, n).astype(np.float32),
    }


def _reference(cols: dict[str, np.ndarray], cap: int) -> tuple[int, dict[str, np.ndarray]]:
    """(count, slots) of one owner: its qualifying rows in table order, the
    first ``cap`` kept, zero past them."""
    ship = cols["l_shipdate"]
    idx = np.flatnonzero((ship >= np.float32(LO)) & (ship < np.float32(HI)))
    slots = {}
    for name, col in cols.items():
        slots[name] = np.zeros(cap, col.dtype)
        slots[name][: min(idx.size, cap)] = col[idx[:cap]]
    return idx.size, slots


def _four_owner_cases() -> dict:
    from repro.launch.mesh import mesh_1d

    mesh = mesh_1d()
    s = mesh.size
    host = _columns(s * ROWS_PER_OWNER)
    table = Table({n: jax.device_put(c, NamedSharding(mesh, P("x"))) for n, c in host.items()})
    out = {}
    for name, (use_pallas, cap) in CASES.items():
        scan = ops.ShardScan(mesh, cap, use_pallas=use_pallas)
        slots, counts, total = scan(table, LO, HI)
        want, wrong = [], 0
        for o in range(s):
            part = {n: c[o * ROWS_PER_OWNER:(o + 1) * ROWS_PER_OWNER] for n, c in host.items()}
            count, ref = _reference(part, cap)
            want.append(count)
            for n in host:
                wrong += int(np.sum(np.asarray(slots[n][o]).view(np.uint32) != ref[n].view(np.uint32)))
        out[name] = {
            "owners": s, "cap": cap, "counts": np.asarray(counts).tolist(), "want": want, "total": total,
            "cells_wrong": wrong, "shape": list(slots["l_shipdate"].shape),
            "consumer": all(slots[n].devices() == {scan.consumer} for n in host),
            "requests": scan.exchange.requests, "bytes_exchanged": scan.exchange.bytes_exchanged,
            "overflows": scan.exchange.overflows,
        }
    return out


@pytest.fixture(scope="module")
def four_owners() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    p = subprocess.run([sys.executable, __file__], env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", [c for c in CASES if "overflow" not in c])
def test_four_owners_match_the_reference(four_owners, case):
    got = four_owners[case]
    assert got["owners"] == 4 and got["shape"] == [4, got["cap"]] and got["consumer"]
    assert got["counts"] == got["want"] and got["total"] == sum(got["want"])
    assert all(0 < c <= got["cap"] for c in got["counts"])
    assert got["cells_wrong"] == 0  # every slot bit for bit, zero past each count
    assert got["overflows"] == 0


def test_undersized_capacity_reports_the_overflow(four_owners):
    got = four_owners["kernel-overflow"]
    assert got["counts"] == got["want"] and all(c > got["cap"] for c in got["counts"])
    assert got["overflows"] == 4
    assert got["cells_wrong"] == 0  # each owner kept its first `cap` rows


@pytest.mark.parametrize("case", list(CASES))
def test_bytes_exchanged_counts_every_other_owners_buffer_and_count(four_owners, case):
    got = four_owners[case]
    assert got["requests"] == 1
    assert got["bytes_exchanged"] == (4 - 1) * 4 * got["cap"] * 4 + (4 - 1) * 4


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("cap", [6_000, 600_000], ids=["resident", "streaming"])
def test_one_device_mesh_gives_what_compact_gives(use_pallas, cap):
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    table = Table({n: jax.numpy.asarray(c) for n, c in _columns(ROWS_PER_OWNER).items()})
    slots, counts = jax.jit(lambda t: ops.shard_compact(t, LO, HI, cap, mesh, use_pallas=use_pallas))(table)
    out, cnt = ops.compact(table, ops.pred_between(table["l_shipdate"], LO, HI), cap, use_pallas=use_pallas)
    assert np.asarray(counts).tolist() == [int(cnt)]
    for n in table.names:
        assert slots[n].shape == (1, cap)
        np.testing.assert_array_equal(np.asarray(slots[n][0]), np.asarray(out[n]))


if __name__ == "__main__":
    print(json.dumps(_four_owner_cases()))
