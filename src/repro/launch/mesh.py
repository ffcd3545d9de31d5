"""Mesh construction + logical-axis sharding rules.

Every init function in models/ has a mirror `*_specs` returning tuples of
*logical* axis names per parameter dim. This module maps logical axes to
mesh axes per (arch, mesh, shape-cell):

  batch      -> ("pod","data")        activations' leading dim (DP)
  embed      -> ("data",)+pod if fsdp  ZeRO-3-style param sharding
  heads/mlp/vocab/inner/ssm_heads -> "model"   tensor parallelism
  experts    -> "model" when E % model == 0 (EP), else expert_ff -> "model"
  kv_heads   -> replicated (GQA kv=8 < 16-way model axis)
  cache_seq  -> "model" (+ "data" when batch can't shard, e.g. long_500k B=1)

ZeRO-1 is applied on top for optimizer moments: the largest still-free dim
divisible by the data-axis size gets the data axes.

`make_production_mesh` is a function (never module-level) so importing this
file touches no jax device state.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell
from repro.models.moe import expert_sharding


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over actually-present devices (tests / CPU smoke)."""
    return jax.make_mesh((data, model), ("data", "model"))


def mesh_1d() -> Mesh:
    """A 1-D mesh ``x`` over every device of the process, in
    ``jax.devices()`` order: the network collectives and the sharded
    pushdown scan run on it."""
    devs = jax.devices()
    return Mesh(np.array(devs).reshape(len(devs)), ("x",))


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Rules:
    """Logical-axis name -> mesh axes (None = replicate)."""

    table: dict[str, Any]

    def spec(self, axes: tuple) -> P:
        return P(*[self.table.get(a) for a in axes])

    def tree_specs(self, spec_tree: Any) -> Any:
        """Map a logical-axes pytree -> PartitionSpec pytree."""
        return jax.tree_util.tree_map(
            lambda axes: self.spec(axes), spec_tree, is_leaf=_is_axes
        )

    def shardings(self, mesh: Mesh, spec_tree: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda axes: NamedSharding(mesh, self.spec(axes)), spec_tree, is_leaf=_is_axes
        )


def _is_axes(v: Any) -> bool:
    return isinstance(v, tuple) and all(a is None or isinstance(a, str) for a in v)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def logical_rules(cfg: ArchConfig, mesh: Mesh, cell: ShapeCell | None = None) -> Rules:
    has_pod = "pod" in mesh.axis_names
    data_axes: Any = ("pod", "data") if has_pod else ("data",)
    n_data = int(np.prod([_axis_size(mesh, a) for a in data_axes]))
    n_model = _axis_size(mesh, "model")

    batch_axes: Any = data_axes
    cache_seq: Any = ("model",)
    if cell is not None and cell.global_batch % max(n_data, 1) != 0:
        # batch too small for DP (long_500k B=1): spread the cache/sequence
        # over the data axes instead and replicate the batch.
        batch_axes = None
        cache_seq = data_axes + ("model",)

    ep = expert_sharding(cfg, n_model) if cfg.is_moe else "ep"
    fsdp_axes = data_axes if cfg.fsdp else None

    table: dict[str, Any] = {
        "batch": batch_axes,
        "embed": fsdp_axes,
        "heads": "model",
        "kv_heads": None,
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "experts": "model" if ep == "ep" else None,
        "expert_ff": None if ep == "ep" else "model",
        "layers": None,
        "cache_seq": cache_seq,
        "inner": "model",
        "ssm_heads": "model",
        "conv_ch": None,
        "seq": None,
    }
    return Rules(table)


# ---------------------------------------------------------------------------
def batch_pspecs(cfg: ArchConfig, cell: ShapeCell, rules: Rules) -> dict[str, P]:
    """PartitionSpec per input-batch entry (matches models.model.input_specs)."""
    b = rules.table["batch"]
    if cell.kind == "train":
        if cfg.encoder_decoder:
            return {"frames": P(b, None, None), "tgt_tokens": P(b, None), "labels": P(b, None)}
        inp = P(b, None) if cfg.embed_inputs else P(b, None, None)
        pos = P(None, b, None) if cfg.rope == "mrope" else P(b, None)
        return {"inputs": inp, "labels": P(b, None), "positions": pos}
    if cell.kind == "prefill":
        if cfg.encoder_decoder:
            return {"frames": P(b, None, None), "tgt_tokens": P(b, None)}
        inp = P(b, None) if cfg.embed_inputs else P(b, None, None)
        pos = P(None, b, None) if cfg.rope == "mrope" else P(b, None)
        return {"inputs": inp, "positions": pos}
    # decode
    if cfg.encoder_decoder or cfg.embed_inputs:
        return {"tokens": P(b, None)}
    return {"tokens": P(b, None, None)}


def zero1_specs(
    state_logical: Any, state_abstract: Any, rules: Rules, mesh: Mesh
) -> Any:
    """PartitionSpecs for optimizer state: base rules + shard the largest
    still-replicated dim over the data axes (ZeRO-1)."""
    has_pod = "pod" in mesh.axis_names
    data_axes = ("pod", "data") if has_pod else ("data",)
    n_data = int(np.prod([_axis_size(mesh, a) for a in data_axes]))

    def one(axes, ab):
        spec = list(rules.spec(axes))
        spec += [None] * (len(ab.shape) - len(spec))
        used = {a for s in spec if s for a in (s if isinstance(s, tuple) else (s,))}
        if "data" in used or n_data <= 1:
            return P(*spec)
        # largest free, divisible dim gets the data axes
        cands = [
            (ab.shape[i], i)
            for i in range(len(ab.shape))
            if spec[i] is None and ab.shape[i] % n_data == 0 and ab.shape[i] >= n_data
        ]
        if cands:
            _, i = max(cands)
            spec[i] = data_axes if len(data_axes) > 1 else data_axes[0]
        return P(*spec)

    return jax.tree_util.tree_map(one, state_logical, state_abstract, is_leaf=_is_axes)


def named(mesh: Mesh, spec_tree: Any) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda v: isinstance(v, P)
    )


def zero3_gather_hook(rules: Rules, param_logical: Any, mesh: Mesh):
    """fn(params)->params that strips data axes off FSDP-sharded params via
    with_sharding_constraint (explicit ZeRO-3 weight gathering).

    Left to itself, the SPMD partitioner may satisfy a contraction whose
    contracting dim is data-sharded (params with logical "embed" under FSDP)
    by all-reducing the partial-sum ACTIVATIONS over the data axis — orders
    of magnitude more wire than gathering the weights. Constraining each
    such parameter to its data-axis-free spec forces the (cheap) weight
    all-gather; the constraint's transpose reduce-scatters the gradient —
    the canonical ZeRO-3 dataflow, with at-use gathering under the layer
    scan (weights gathered per step, not held resident).
    """
    has_pod = "pod" in mesh.axis_names
    data_axes = {"pod", "data"} if has_pod else {"data"}

    def strip(axes_spec):
        spec = rules.spec(axes_spec)
        out = []
        changed = False
        for entry in spec:
            parts = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(a for a in parts if a is not None and a not in data_axes)
            if len(kept) != len([a for a in parts if a is not None]):
                changed = True
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        return P(*out) if changed else None

    strip_tree = jax.tree_util.tree_map(strip, param_logical, is_leaf=_is_axes)
    # P is a tuple subclass and None an empty pytree: flatten explicitly.
    strip_leaves = jax.tree_util.tree_leaves(
        strip_tree, is_leaf=lambda v: v is None or isinstance(v, P)
    )

    def hook(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        assert len(leaves) == len(strip_leaves), (len(leaves), len(strip_leaves))
        out = [
            w if s is None else jax.lax.with_sharding_constraint(w, s)
            for w, s in zip(leaves, strip_leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    return hook
