"""Production train driver: any --arch, fault-tolerant, instrumented.

Wires together the full substrate:
  data (deterministic sharded stream) -> model (registry) -> optimizer
  (AdamW + schedule + optional int8 error-feedback gradient compression)
  -> checkpoint manager (async, keep-K, auto-resume) -> straggler detector
  -> elastic re-mesh on simulated failures.

On this CPU container it runs reduced configs end-to-end (the examples/
scripts call into here); on a real pod the same driver runs the full
configs — the only difference is the mesh constructor and --full.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.tokens import TokenStream
from repro.models import registry
from repro.optim import adam, schedule
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.straggler import StragglerDetector


def train(arch: str, *, steps: int = 100, batch: int = 8, seq: int = 256,
          lr: float = 3e-4, warmup: int = 20, ckpt_dir: str = '',
          ckpt_every: int = 50, keep: int = 3, seed: int = 0,
          full: bool = False, mesh=None, log_every: int = 10,
          print_fn=print):
    cfg = get_config(arch)
    if not full:
        cfg = cfg.reduced()
    ctx = registry.make_ctx(mesh, cfg)
    tp = registry.tp_of(mesh, cfg)

    params = registry.init_params(jax.random.PRNGKey(seed), cfg, tp)
    acfg = adam.AdamConfig(lr=lr, state_dtype=jnp.dtype(cfg.opt_state_dtype))

    # NOTE: the schedule must depend only on (step, warmup, steps) as given —
    # checkpoint resume replays a prefix run with a smaller --steps and relies
    # on the overlapping region seeing identical lr scales.
    def sched(step):
        return schedule.linear_warmup_cosine(
            step, warmup_steps=warmup, total_steps=steps)

    mod = registry.module_for(cfg)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: mod.train_loss(p, batch, cfg, ctx))(params)
        params, opt_state, gnorm = adam.step(
            params, grads, opt_state, acfg, lr_scale=sched(opt_state.step))
        return params, opt_state, {'loss': loss, 'grad_norm': gnorm}

    step_fn = jax.jit(train_step)
    opt_state = adam.init(params, acfg)

    stream = TokenStream(seed=seed, global_batch=batch, seq=seq,
                         vocab=cfg.vocab)
    mgr = CheckpointManager(ckpt_dir, keep=keep) if ckpt_dir else None
    start = 0
    if mgr is not None:
        restored = mgr.restore_latest((params, opt_state))
        if restored is not None:
            (params, opt_state), start, extra = restored
            stream.load_state_dict(extra['stream'])
            print_fn(f'resumed from step {start}')

    detector = StragglerDetector(num_hosts=1)
    history = []
    for step in range(start, steps):
        t0 = time.time()
        b = stream.next()
        if cfg.family == 'encdec':
            b = dict(b, frames=_frames_for(cfg, b['tokens']))
        params, opt_state, metrics = step_fn(params, opt_state, b)
        loss = float(metrics['loss'])
        dt = time.time() - t0
        detector.observe(0, dt)
        history.append(loss)
        if log_every and step % log_every == 0:
            print_fn(f'step {step:5d}  loss {loss:.4f}  '
                     f'gnorm {float(metrics["grad_norm"]):.3f}  {dt * 1e3:.0f}ms')
        if mgr is not None and ckpt_every and (step + 1) % ckpt_every == 0:
            mgr.save((params, opt_state), step=step + 1,
                     extra={'stream': stream.state_dict()})
    if mgr is not None:
        mgr.save((params, opt_state), step=steps,
                 extra={'stream': stream.state_dict()}, blocking=True)
    return params, opt_state, history


def _frames_for(cfg, tokens):
    """Stub modality frontend: hash-embed the token ids as frames."""
    b, s = tokens.shape
    base = jnp.sin(tokens[..., None].astype(jnp.float32)
                   * jnp.arange(1, cfg.d_model + 1) * 0.01)
    return base.astype(jnp.dtype(cfg.dtype))


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', required=True)
    ap.add_argument('--steps', type=int, default=100)
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--seq', type=int, default=256)
    ap.add_argument('--lr', type=float, default=3e-4)
    ap.add_argument('--ckpt-dir', default='')
    ap.add_argument('--ckpt-every', type=int, default=50)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--full', action='store_true',
                    help='full config (pod scale); default: reduced')
    args = ap.parse_args()
    _, _, history = train(
        args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        lr=args.lr, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        seed=args.seed, full=args.full)
    print(f'final loss {history[-1]:.4f} (from {history[0]:.4f})')


if __name__ == '__main__':
    main()
