"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel directory has: the pallas_call + BlockSpec implementation,
``ops.py`` (jit'd wrapper with impl switch), ``ref.py`` (pure-jnp oracle).
``impl``/``interpret`` default to the process-wide policy in
:mod:`repro.kernels.policy` (``REPRO_KERNEL=pallas|xla`` env override,
else Pallas compiled on TPU and XLA elsewhere; interpret mode auto-selects
off-TPU so the CPU test container exercises kernel bodies unchanged).
"""
from . import policy  # noqa: F401
from .delta_apply import (FusedOut, delta_apply_chain,  # noqa: F401
                          delta_apply_chain_batched, delta_apply_chain_prefix,
                          delta_apply_chain_prefix_batched, delta_apply_fused,
                          delta_apply_fused_batched)
from .segment_sum import bucket_edges, segment_sum  # noqa: F401
