"""What the Pallas wrappers derive from the platform, in one place."""
from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas import tpu as pltpu

_MIB = 1024 * 1024
# Mosaic's default scoped-VMEM limit on v5e, and the most a kernel may ask
# for (v5e has 128 MiB of VMEM per core; the rest stays with XLA).
_SCOPED_VMEM_DEFAULT = 16 * _MIB
_SCOPED_VMEM_MAX = 100 * _MIB


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode: off on a TPU, on everywhere else (off the chip
    the interpreter is the only way a kernel runs).  ``None`` derives it
    from the platform; tests pass ``True`` explicitly.  Interpret mode is
    refused on a TPU: it would time an emulation of the kernel."""
    on_tpu = jax.default_backend() == 'tpu'
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError('Pallas interpret mode requested on a TPU')
    return bool(interpret)


def vmem_compiler_params(resident_bytes: int) -> pltpu.CompilerParams:
    """Compiler params whose scoped-VMEM limit holds ``resident_bytes`` of
    blocks and scratch twice over (the pipeline double-buffers streamed
    blocks), never below Mosaic's default and never above the cap."""
    limit = min(max(_SCOPED_VMEM_DEFAULT, 2 * resident_bytes),
                _SCOPED_VMEM_MAX)
    return pltpu.CompilerParams(vmem_limit_bytes=int(limit))
