"""Pallas TPU kernels for the performance-critical compute layers.

Each kernel lives in its own subpackage with:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, calibration, layer helpers)
  ref.py    — pure-jnp oracle used by the test sweeps

Interpret mode is derived from the platform (``_platform.resolve_interpret``):
kernels are compiled by Mosaic on a TPU and interpreted on the CPU, where
tests validate them.  The production dry-run uses the pure-JAX equivalents
(``use_pallas=False``) since the CPU backend cannot lower Mosaic kernels.
"""
from . import flash_attention, lstm_gates, lstm_seq, quant_matmul

__all__ = ['flash_attention', 'lstm_gates', 'lstm_seq', 'quant_matmul']
