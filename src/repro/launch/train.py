"""Train step factory + sharded train-state construction.

``make_train_step`` builds the jit-able update: loss -> grad (with microbatch
gradient accumulation — the compute/communication overlap lever at scale) ->
global-norm clip -> optimizer -> apply.  ``abstract_train_state`` builds
ShapeDtypeStructs + NamedShardings without allocating anything (the 1T-param
configs can never be materialised on the host).

The end-to-end training driver (data pipeline, checkpointing, fault tolerance)
lives in ``main()`` below; the dry-run imports only the step factory.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import sharding as shd
from ..configs import ArchConfig, ShapeConfig
from ..models import ModelBundle, batch_axes, input_specs
from ..optim import (apply_updates, clip_by_global_norm, cosine_schedule,
                     make_optimizer, optimizer_state_axes, wsd_schedule)
from .mesh import resolve_rules


def lr_schedule_for(cfg: ArchConfig, peak_lr=3e-4, warmup=100, total=10_000):
    if cfg.name.startswith('minicpm'):
        return wsd_schedule(peak_lr, warmup, total)   # MiniCPM trains with WSD
    return cosine_schedule(peak_lr, warmup, total)


def make_train_step(bundle: ModelBundle, optimizer, *, microbatches: int = 1,
                    grad_clip: float = 1.0) -> Callable:
    """(state, batch) -> (state, metrics).  state = {'params','opt','step'}."""

    def grads_of(params, batch):
        return jax.value_and_grad(lambda p: bundle.loss_fn(p, batch))(params)

    def train_step(state, batch):
        params = state['params']
        if microbatches == 1:
            loss, grads = grads_of(params, batch)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mb = jax.tree.map(split, batch)

            def mb_step(acc, b):
                loss_i, g = grads_of(params, b)
                acc = jax.tree.map(lambda a, x: a + x.astype(a.dtype), acc[0], g), \
                    acc[1] + loss_i
                return acc, None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
            (gsum, lsum), _ = jax.lax.scan(mb_step, (zeros, 0.0), mb)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches

        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        updates, opt_state = optimizer.update(grads, state['opt'], params)
        params = apply_updates(params, updates)
        new_state = {'params': params, 'opt': opt_state,
                     'step': state['step'] + 1}
        return new_state, {'loss': loss, 'grad_norm': gnorm}

    return train_step


def abstract_init(init_fn: Callable, *args) -> Tuple[Any, Any]:
    """eval_shape an init that returns (arrays, axes) — axes (a string pytree)
    cannot cross the tracer, so they are captured by side channel."""
    box = {}

    def arrays_only(*a):
        out, axes = init_fn(*a)
        box['axes'] = axes
        return out

    sds = jax.eval_shape(arrays_only, *args)
    return sds, box['axes']


def abstract_train_state(bundle: ModelBundle, mesh, rules_dict,
                         lr_fn=None) -> Tuple[Any, Any, Any]:
    """Returns (state_sds, state_shardings, optimizer) with zero allocation."""
    cfg = bundle.cfg
    lr_fn = lr_fn or lr_schedule_for(cfg)
    optimizer = make_optimizer(cfg.optimizer, lr_fn)

    params_sds, axes = abstract_init(bundle.init, jax.random.PRNGKey(0))
    opt_sds = jax.eval_shape(optimizer.init, params_sds)
    opt_axes = optimizer_state_axes(cfg.optimizer, axes)

    rules = shd.ShardingRules(mesh, resolve_rules(rules_dict, mesh))
    p_sh = shd.param_sharding_tree(axes, params_sds, mesh, rules.rules)
    o_sh = shd.param_sharding_tree(opt_axes, opt_sds, mesh, rules.rules)
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())
    state_sds = {'params': params_sds, 'opt': opt_sds,
                 'step': jax.ShapeDtypeStruct((), jnp.int32)}
    state_sh = {'params': p_sh, 'opt': o_sh, 'step': repl}
    return state_sds, state_sh, optimizer


def batch_shardings(cfg: ArchConfig, shape: ShapeConfig, mesh, rules_dict):
    rules = shd.ShardingRules(mesh, resolve_rules(rules_dict, mesh))
    ax = batch_axes(cfg, shape)
    specs = input_specs(cfg, shape)
    return {k: rules.sharding(ax[k], specs[k].shape) for k in specs}


# ----------------------------------------------------------------- driver
def local_mesh():
    """Largest (data, model) mesh the local devices support."""
    n = len(jax.devices())
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and n >= m:
            model = m
            break
    import numpy as np
    from jax.sharding import AxisType, Mesh
    return Mesh(
        np.array(jax.devices()).reshape(n // model, model),
        ('data', 'model'), axis_types=(AxisType.Auto, AxisType.Auto))


def main(argv=None):
    """End-to-end training driver: data -> step -> checkpoint, fault-tolerant.

    python -m repro.launch.train --arch chipmunk-ctc --steps 50 --smoke
    """
    import argparse
    import time as _time

    from .. import configs
    from ..checkpoint import CheckpointManager
    from ..compile_cache import enable_compile_cache
    from ..data import ShardedLoader, source_for
    from ..models import get_bundle
    from ..runtime import FaultConfig, FaultTolerantRunner

    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='chipmunk-ctc')
    ap.add_argument('--steps', type=int, default=20)
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--seq', type=int, default=64)
    ap.add_argument('--lr', type=float, default=1e-3)
    ap.add_argument('--smoke', action='store_true',
                    help='use the reduced config (CPU-runnable)')
    ap.add_argument('--ckpt-dir', default='/tmp/repro_ckpt')
    ap.add_argument('--ckpt-every', type=int, default=20)
    ap.add_argument('--resume', action='store_true')
    ap.add_argument('--microbatches', type=int, default=1)
    ap.add_argument('--log-every', type=int, default=5)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    shape = configs.ShapeConfig('cli', 'train', args.seq, args.batch)
    bundle = get_bundle(cfg)
    mesh = local_mesh()
    rules_dict = shd.TRAIN_RULES

    rules = shd.ShardingRules(mesh, resolve_rules(rules_dict, mesh))
    with shd.use_rules(rules):
        state_sds, state_sh, optimizer = abstract_train_state(
            bundle, mesh, rules_dict,
            lr_fn=cosine_schedule(args.lr, warmup=10, total=args.steps))
        step_fn = jax.jit(
            make_train_step(bundle, optimizer,
                            microbatches=args.microbatches),
            in_shardings=(state_sh, None), donate_argnums=(0,))

        # real init (small configs only — big ones go through the dry-run)
        params, _ = bundle.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, state_sh['params'])
        opt_state = jax.device_put(optimizer.init(params), state_sh['opt'])
        state = {'params': params, 'opt': opt_state,
                 'step': jnp.zeros((), jnp.int32)}

        ckpt = CheckpointManager(args.ckpt_dir)
        start = 0
        if args.resume and ckpt.latest_step() is not None:
            state = ckpt.restore(state, shardings=state_sh)
            start = int(state['step'])
            print(f'resumed at step {start}')

        loader = ShardedLoader(
            source_for(cfg, shape), shape,
            batch_shardings(cfg, shape, mesh, rules_dict), start_step=start)
        runner = FaultTolerantRunner(
            step_fn, ckpt_manager=ckpt,
            cfg=FaultConfig(heartbeat_path=f'{args.ckpt_dir}/heartbeat.json'),
            restore_fn=lambda: ckpt.restore(state_sds, shardings=state_sh))

        t0 = _time.time()
        for i, (step, batch) in zip(range(start, args.steps), loader):
            state, metrics = runner.run_step(step, state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f'step {step:5d} loss {float(metrics["loss"]):8.4f} '
                      f'gnorm {float(metrics["grad_norm"]):8.3f} '
                      f'({(_time.time()-t0)/(i-start+1):.2f}s/step)')
            if (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state)
        ckpt.save(args.steps, state, blocking=True)
        loader.close()
        print(f'done; events: {runner.events[:5]}')


if __name__ == '__main__':
    main()
