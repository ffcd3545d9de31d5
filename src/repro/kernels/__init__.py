"""Pallas TPU kernels (interpret mode on the CPU, compiled on a TPU) +
pure-jnp oracles.

Public API lives in repro.kernels.ops: flash_attention, decode_attention,
ssd_intra, gmm, filter_agg, group_filter_agg(_multi), block_compact — each
with a use_pallas=False oracle path.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
