"""The main path's Pallas kernels compile for a TPU v5e at TPC-H SF1 widths.

Nothing runs: each test compiles one kernel with ``interpret=False`` for a
described (not attached) v5e chip, which refuses what the chip's compiler
would refuse (unsupported primitives, unaligned slices, too much VMEM).
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one that runs this file
loads the TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import block_compact, group_filter_agg, ops

ROWS = 6_000_000  # lineitem at SF1
GROUP_BLOCK = 16384  # kernels.ops default block_n for the scan kernels
COMPACT_BLOCK = 65536  # kernels.ops default block_n for block_compact
CHUNK = 1 << 21  # kernels.ops default chunk_n for streamed compaction


def _padded(block: int) -> int:
    return -(-ROWS // block) * block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (groups, columns, predicates, aggregates, programs) of the fused q6, q1
# and q12 plans, the serving batches of eight requests of each, and the
# pushdown task's aggregate at the data.
@pytest.mark.parametrize(
    "groups,cols,preds,aggs,progs",
    [(1, 4, 3, 1, 1), (6, 5, 1, 5, 1), (7, 4, 3, 2, 1), (7, 4, 3, 2, 8), (1, 2, 1, 1, 1),
     (6, 5, 1, 5, 8), (1, 4, 3, 1, 8)],
    ids=["q6", "q1", "q12", "q12-batch8", "pushdown", "q1-batch8", "q6-batch8"],
)
def test_group_filter_agg_compiles(shape, groups, cols, preds, aggs, progs):
    n = _padded(GROUP_BLOCK)
    _assert_kernel_compiles(
        lambda c, k, po, pc, ao, ac: group_filter_agg.group_filter_agg(
            c, k, po, pc, ao, ac, num_groups=groups, block_n=GROUP_BLOCK
        ),
        shape((cols, n), jnp.float32),
        shape((1, n), jnp.int32),
        shape((preds, 3), jnp.int32),
        shape((progs, preds, 2), jnp.float32),
        shape((aggs, 2 * group_filter_agg.MAX_TERMS), jnp.int32),
        shape((progs, aggs, group_filter_agg.MAX_TERMS), jnp.float32),
    )


@pytest.mark.parametrize("cap", [90_000, 523_775], ids=["sel0.01", "budget-edge"])
def test_block_compact_resident_compiles(shape, cap):
    assert block_compact.resident_bytes(4, cap) <= ops.VMEM_BUDGET_BYTES
    n = _padded(COMPACT_BLOCK)
    _assert_kernel_compiles(
        lambda c, m: block_compact.block_compact(c, m, cap, block_n=COMPACT_BLOCK),
        shape((4, n), jnp.float32),
        shape((1, n), jnp.int32),
    )


def test_block_compact_stream_chunk_compiles(shape):
    cap = 4_500_000  # pushdown at selectivity 0.5 over SF1
    out, st, carry = jax.eval_shape(lambda: block_compact.stream_init(4, cap))
    _assert_kernel_compiles(
        lambda o, s, r, c, m: block_compact.stream_chunk(
            (o, s, r), c, m, cap, block_n=COMPACT_BLOCK
        ),
        shape(out.shape, out.dtype),
        shape(st.shape, st.dtype),
        shape(carry.shape, carry.dtype),
        shape((4, CHUNK), jnp.float32),
        shape((1, CHUNK), jnp.int32),
    )


def test_shard_compact_compiles_for_four_chips(topo, monkeypatch):
    """The sharded pushdown scan on a 2x2 mesh: the streaming kernel on
    every owner and one collective-permute pair a round into chip 0."""
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.engine import ops as engine_ops
    from repro.engine.table import Table

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)  # compile for the chip, not the interpreter
    mesh = Mesh(np.array(topo.devices).reshape(4), ("x",))
    rows, cap = 4 * 2 * CHUNK, 600_000  # two chunks an owner, past the VMEM budget
    cols = ("l_discount", "l_extendedprice", "l_quantity", "l_shipdate")
    table = Table({c: jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=NamedSharding(mesh, P("x")))
                   for c in cols})
    bound = jax.ShapeDtypeStruct((), jnp.float32, sharding=NamedSharding(mesh, P()))
    scan = functools.partial(engine_ops.shard_compact, cap=cap, mesh=mesh)
    text = jax.jit(scan).lower(table, bound, bound).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "collective-permute" in text and "all-gather" not in text
