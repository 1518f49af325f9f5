"""Batched serving driver: continuous-batching decode loop for any --arch.

A deliberately small but real serving core:
  * request queue with Poisson-ish deterministic arrivals;
  * **continuous batching**: finished slots are refilled between decode
    steps (the KV cache slot is reassigned; its `pos` tracks per-slot);
  * prefill-on-admit (one prefill per admitted request, its KV written
    into the slot), then one fused decode step per tick for all slots;
  * greedy sampling with a per-request max-token budget.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --slots 4 --requests 12 --max-new 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.tokens import synthetic_tokens
from repro.models import registry
from repro.runtime.compile_cache import use_compile_cache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: jnp.ndarray
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    admitted_at: float = 0.0
    done_at: float = 0.0


class Server:
    """Slot-based continuous batching over the registry's serve steps."""

    def __init__(self, arch: str, *, slots: int = 4, max_seq: int = 512,
                 full: bool = False, mesh=None):
        cfg = get_config(arch)
        if not full:
            cfg = cfg.reduced()
        self.cfg = cfg
        self.ctx = registry.make_ctx(mesh, cfg)
        tp = registry.tp_of(mesh, cfg)
        self.params = registry.init_params(jax.random.PRNGKey(0), cfg, tp)
        self.slots = slots
        self.max_seq = max_seq

        self.decode_fn = jax.jit(registry.make_decode_step(cfg, self.ctx))
        self.state = registry.init_decode_state(cfg, slots, max_seq, tp)
        self.slot_req: list[Optional[Request]] = [None] * slots
        self.slot_pos = [0] * slots
        self.cur_tok = jnp.zeros((slots, 1), jnp.int32)

        # per-slot prefill: write the prompt's KV into this slot via the
        # decode step (teacher-forcing loop) — simple and always correct
        # for every family (ssm/hybrid carry recurrent state the same way).

    def admit(self, req: Request, slot: int) -> None:
        req.admitted_at = time.time()
        self.slot_req[slot] = req
        self.slot_pos[slot] = 0
        if self.cfg.family == 'ssm':
            # recurrent state: zero this slot's entries
            self.state = jax.tree.map(
                lambda a: a.at[..., slot, :, :, :].set(0.0)
                if a.ndim >= 4 else a, self.state)
        # feed the prompt token-by-token through the decode step
        for t in range(req.prompt.shape[0]):
            tok = jnp.zeros((self.slots, 1), jnp.int32).at[slot, 0].set(
                req.prompt[t])
            tok = jnp.where(jnp.arange(self.slots)[:, None] == slot,
                            tok, self.cur_tok)
            logits, self.state = self.decode_fn(
                self.params, tok, self.state, jnp.int32(self.slot_pos[slot]))
            self.slot_pos[slot] += 1
        nxt = int(jnp.argmax(logits[slot]))
        self.cur_tok = self.cur_tok.at[slot, 0].set(nxt)
        req.out.append(nxt)

    def step(self) -> list[Request]:
        """One fused decode tick for all active slots.

        Returns the requests that finished on this tick (their slots are
        freed and can be refilled before the next tick).
        """
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return []
        pos = max(self.slot_pos[i] for i in active)
        logits, self.state = self.decode_fn(
            self.params, self.cur_tok, self.state, jnp.int32(pos))
        nxt = jnp.argmax(logits, axis=-1)
        finished = []
        for i in active:
            r = self.slot_req[i]
            tok = int(nxt[i])
            r.out.append(tok)
            self.slot_pos[i] = pos + 1
            if len(r.out) >= r.max_new or self.slot_pos[i] >= self.max_seq - 1:
                r.done_at = time.time()
                self.slot_req[i] = None
                finished.append(r)
        self.cur_tok = nxt[:, None].astype(jnp.int32)
        return finished

    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]


def run(arch: str, *, slots: int = 4, n_requests: int = 8,
        prompt_len: int = 8, max_new: int = 16, max_seq: int = 256,
        print_fn=print) -> dict:
    server = Server(arch, slots=slots, max_seq=max_seq)
    cfg = server.cfg
    pending = [
        Request(rid=i,
                prompt=synthetic_tokens(7, i, 1, prompt_len, cfg.vocab)[0],
                max_new=max_new)
        for i in range(n_requests)
    ]
    done: list[Request] = []
    t0 = time.time()
    ticks = 0
    while pending or any(server.slot_req):
        for slot in server.free_slots():
            if not pending:
                break
            server.admit(pending.pop(0), slot)
        done.extend(server.step())
        ticks += 1
        if ticks > 10000:
            raise RuntimeError('serve loop did not drain')
    dt = time.time() - t0
    # tokens actually emitted (requests can stop early at max_seq)
    total_tokens = sum(len(r.out) for r in done)
    stats = {'requests': n_requests, 'completed': len(done), 'ticks': ticks,
             'tokens': total_tokens, 'wall_s': dt,
             'tok_per_s': total_tokens / dt}
    print_fn(f'{arch}: {len(done)}/{n_requests} requests, {ticks} ticks, '
             f'{total_tokens} tokens, {stats["tok_per_s"]:.1f} tok/s')
    return stats


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', required=True)
    ap.add_argument('--slots', type=int, default=4)
    ap.add_argument('--requests', type=int, default=8)
    ap.add_argument('--prompt-len', type=int, default=8)
    ap.add_argument('--max-new', type=int, default=16)
    ap.add_argument('--max-seq', type=int, default=256)
    args = ap.parse_args()
    run(args.arch, slots=args.slots, n_requests=args.requests,
        prompt_len=args.prompt_len, max_new=args.max_new,
        max_seq=args.max_seq)


if __name__ == '__main__':
    main()
