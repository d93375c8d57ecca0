"""Public op: padded/validated flash attention entry point."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .._platform import resolve_interpret
from .kernel import flash_attention
from .ref import attention_ref


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
        window: Optional[int] = None, sm_scale: Optional[float] = None,
        use_pallas: bool = True, interpret: Optional[bool] = None,
        bq: int = 128, bk: int = 128) -> jax.Array:
    """Multi-head attention, auto-padding sequence dims to block multiples."""
    if not use_pallas:
        return attention_ref(q, k, v, causal=causal, window=window,
                             sm_scale=sm_scale)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq_eff, bk_eff = min(bq, max(8, sq)), min(bk, max(8, sk))
    pad_q = (-sq) % bq_eff
    pad_k = (-sk) % bk_eff
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = flash_attention(qp, kp, vp, causal=causal, window=window,
                          sm_scale=sm_scale, kv_len=sk if pad_k else None,
                          offset=(sk - sq) if causal else 0,
                          bq=bq_eff, bk=bk_eff,
                          interpret=resolve_interpret(interpret))
    return out[:, :, :sq]
