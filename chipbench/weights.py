"""The PRNG key every model kind draws its weights from (see
``models/<kind>.py``)."""
from __future__ import annotations

import jax
import numpy as np


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (two 32-bit words) and a stream."""
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(words), stream)
