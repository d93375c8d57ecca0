"""Layer pipelining over systolic sub-arrays — Chipmunk contribution C3b.

The paper's best configuration (3x(5x5)) dedicates one 5x5 sub-array per LSTM layer:
after the initial weight load, no reconfiguration ever happens and frames stream
through the three stages.  We map this to a ("stage", "row", "col") mesh: stage s
owns layer s's weight tiles; the hidden state of stage s-1 is handed to stage s via
``lax.ppermute`` (the board-level wiring between sub-arrays).

At global microstep k, stage s processes timestep k - s of layer s (a classic
1F pipeline with S-1 bubbles at fill/drain).  All layers are padded to a common
SystolicPlan so the mesh is rectangular; the silicon instead time-multiplexes tile
positions per engine — see core/perf_model.py for the cycle accounting of that.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .lstm import GATES, I, F, G, O, PEEP_I, PEEP_F, PEEP_O, LSTMParams
from .systolic import PackedLSTM, SystolicPlan, pack_lstm


def pack_pipeline(layers: Sequence[LSTMParams], tile: int) -> Tuple[PackedLSTM, SystolicPlan]:
    """Pack S layers into stacked tiles (S, R, C, 4, t, t) under a common plan.

    Layers with a smaller input dim (e.g. layer 0: N_x=123 vs N_h=421 elsewhere)
    are zero-padded on the input-column side.
    """
    n_h = layers[0].n_h
    assert all(l.n_h == n_h for l in layers), 'pipeline layers must share N_h'
    n_x_max = max(l.n_x for l in layers)
    plan = SystolicPlan(n_x_max, n_h, tile)
    packs = []
    for l in layers:
        lp = LSTMParams(
            w_x=jnp.zeros((GATES, l.w_x.shape[1], n_x_max), l.w_x.dtype
                          ).at[:, :, :l.n_x].set(l.w_x),
            w_h=l.w_h, w_peep=l.w_peep, b=l.b)
        packs.append(pack_lstm(lp, plan))
    stacked = PackedLSTM(
        tiles=jnp.stack([p.tiles for p in packs]),
        peep=jnp.stack([p.peep for p in packs]),
        bias=jnp.stack([p.bias for p in packs]),
        plan_shape=packs[0].plan_shape)
    return stacked, plan


def shard_pipeline(packed: PackedLSTM, mesh: Mesh) -> PackedLSTM:
    return PackedLSTM(
        tiles=jax.device_put(packed.tiles, NamedSharding(mesh, P('stage', 'row', 'col'))),
        peep=jax.device_put(packed.peep, NamedSharding(mesh, P('stage', 'row'))),
        bias=jax.device_put(packed.bias, NamedSharding(mesh, P('stage', 'row'))),
        plan_shape=packed.plan_shape)


def systolic_pipeline(packed: PackedLSTM, mesh: Mesh, xs: jax.Array,
                      stage_axis: str = 'stage', row_axis: str = 'row',
                      col_axis: str = 'col') -> jax.Array:
    """Run S pipelined LSTM layers over xs: (T, B, padded_x) -> (T, B, n_h).

    Requires mesh sizes (S, plan.rows, plan.cols).
    """
    plan = packed.plan
    t = plan.tile
    S = mesh.shape[stage_axis]
    T, B = xs.shape[0], xs.shape[1]
    assert xs.shape[2] == plan.padded_x
    assert mesh.shape[row_axis] == plan.rows and mesh.shape[col_axis] == plan.cols
    K = T + S - 1  # fill + drain

    def body(tiles, peep, bias, xs_padded):
        w_tile = tiles[0, 0, 0]     # (4, t, t) local block
        peep_r, bias_r = peep[0, 0], bias[0, 0]
        s_idx = jax.lax.axis_index(stage_axis)
        c_idx = jax.lax.axis_index(col_axis)
        fwd = [(i, (i + 1) % S) for i in range(S)]  # stage s -> s+1 (ring; wrap ignored)

        h_own0 = jnp.zeros((B, plan.padded_h), xs.dtype)   # recurrent state h^l
        c_row0 = jnp.zeros((B, t), xs.dtype)

        def step(carry, k_and_x):
            k_idx, x_k = k_and_x
            h_own, c_row = carry
            # Hand the previous step's output of stage s-1 to stage s (Fig. 3 wiring).
            handed = jax.lax.ppermute(h_own, stage_axis, fwd)
            pad = jnp.zeros((B, plan.padded_x - plan.padded_h), xs.dtype) \
                if plan.padded_x > plan.padded_h else None
            handed_x = (jnp.concatenate([handed, pad], axis=1)[:, :plan.padded_x]
                        if pad is not None else handed[:, :plan.padded_x])
            stage_in = jnp.where(s_idx == 0, x_k, handed_x)

            # Column input: x-region columns slice stage_in, h-region columns
            # slice the locally re-broadcast h_own.
            x_off = jnp.minimum(c_idx, plan.cols_x - 1) * t
            x_slice = jax.lax.dynamic_slice(stage_in, (0, x_off), (B, t))
            h_off = jnp.maximum(c_idx - plan.cols_x, 0) * t
            h_slice = jax.lax.dynamic_slice(h_own, (0, h_off), (B, t))
            col_in = jnp.where(c_idx < plan.cols_x, x_slice, h_slice)

            partial = jnp.einsum('gij,bj->bgi', w_tile, col_in)
            pre = jax.lax.psum(partial, col_axis)
            i = jax.nn.sigmoid(pre[:, I] + peep_r[PEEP_I] * c_row + bias_r[I])
            f = jax.nn.sigmoid(pre[:, F] + peep_r[PEEP_F] * c_row + bias_r[F])
            g = jnp.tanh(pre[:, G] + bias_r[G])
            c_new = f * c_row + i * g
            o = jax.nn.sigmoid(pre[:, O] + peep_r[PEEP_O] * c_new + bias_r[O])
            h_new = o * jnp.tanh(c_new)
            h_full = jax.lax.all_gather(h_new, row_axis, axis=1, tiled=True)
            # Stages idle until their first real input arrives (pipeline fill).
            active = k_idx >= s_idx
            h_own = jnp.where(active, h_full, h_own)
            c_row = jnp.where(active, c_new, c_row)
            return (h_own, c_row), h_own

        ks = jnp.arange(K)
        (_, _), hs = jax.lax.scan(step, (h_own0, c_row0), (ks, xs_padded))
        # Keep only the last stage's view (identical across row/col by psum/gather).
        out = jnp.where(s_idx == S - 1, hs, jnp.zeros_like(hs))
        return jax.lax.psum(out, stage_axis)  # (K, B, padded_h), replicated

    xs_padded = jnp.concatenate(
        [xs, jnp.zeros((S - 1, B, plan.padded_x), xs.dtype)], axis=0)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(stage_axis, row_axis, col_axis),
                  P(stage_axis, row_axis), P(stage_axis, row_axis),
                  P(None, None, None)),
        out_specs=P(None, None, None),
        check_vma=False,
    )
    hs = fn(packed.tiles, packed.peep, packed.bias, xs_padded)  # (K, B, Ph)
    return hs[S - 1:K, :, :plan.n_h]
