"""Training driver — fault-tolerant, restart-exact, multi-host ready.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --steps 300 --batch 8 --seq 256 --reduced --ckpt-dir /tmp/ckpt

Features exercised end-to-end (scaled to this container on --reduced):
* deterministic sharded data (restart reproduces the exact token stream)
* AdamW + cosine/WSD schedule, global-norm clipping
* atomic async checkpointing + ``--resume`` auto-restart
* per-step watchdog (straggler detection: a step exceeding
  ``--straggler-factor`` x the trailing median is logged and counted —
  on a real pod this triggers the backup-replica path)
* preemption hook (SIGTERM -> final checkpoint -> clean exit)
* Guardian fencing on the training data paths (--guard / --no-guard)
"""

from __future__ import annotations

import argparse
import json
import signal
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "constant"])
    ap.add_argument("--no-guard", action="store_true")
    ap.add_argument("--policy", default="bitwise",
                    choices=["bitwise", "modulo", "check"])
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stop-after", type=int, default=0,
                    help="simulate preemption: checkpoint and exit after "
                         "this step (schedule still spans --steps)")
    args = ap.parse_args()

    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()

    from repro.checkpoint import CheckpointStore
    from repro.configs import ShapeConfig, get_config
    from repro.core.fence import FencePolicy
    from repro.data import DataConfig, make_source
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import make_guard
    from repro.models import get_model
    from repro.optim import adamw, apply_updates, constant, cosine, wsd

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = get_model(cfg)
    mesh = make_local_mesh()
    shape = ShapeConfig("train", "train", args.seq, args.batch)
    guard = make_guard(cfg, shape, FencePolicy(args.policy),
                       enabled=not args.no_guard)

    sched = {"cosine": lambda: cosine(args.lr, args.steps // 10,
                                      args.steps),
             "wsd": lambda: wsd(args.lr, args.steps // 10,
                                int(args.steps * 0.7),
                                args.steps - args.steps // 10
                                - int(args.steps * 0.7)),
             "constant": lambda: constant(args.lr)}[args.schedule]()
    opt = adamw(sched)

    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed,
                          host_index=jax.process_index(),
                          host_count=jax.process_count())
    source = make_source(data_cfg)

    params = api.init(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)
    start_step = 0

    store = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    if store and args.resume and store.latest_step() is not None:
        (params, opt_state), start_step = store.restore(
            (params, opt_state))
        print(f"resumed from step {start_step}")

    @jax.jit
    def train_step(params, opt_state, batch):
        def loss_of(p):
            return api.loss(p, batch, guard=guard, remat=False)
        loss, grads = jax.value_and_grad(loss_of)(params)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state2, loss

    # preemption hook: checkpoint on SIGTERM, then exit cleanly
    preempted = {"flag": False}

    def on_sigterm(_sig, _frm):
        preempted["flag"] = True
    signal.signal(signal.SIGTERM, on_sigterm)

    times, stragglers = [], 0
    losses = []
    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = {k: jnp.asarray(v)
                 for k, v in source.batch(step).items()}
        params, opt_state, loss = train_step(params, opt_state, batch)
        loss = float(loss)
        dt = time.time() - t0
        times.append(dt)
        losses.append(loss)
        if len(times) > 10:
            med = statistics.median(times[-50:])
            if dt > args.straggler_factor * med:
                stragglers += 1
                print(f"[watchdog] step {step} took {dt:.3f}s "
                      f"(median {med:.3f}s) — straggler #{stragglers}")
        if step % args.log_every == 0:
            print(f"step {step:6d} loss {loss:.4f} "
                  f"({dt * 1e3:.0f} ms/step)")
        if store and (step + 1) % args.ckpt_every == 0:
            store.save_async(step + 1, (params, opt_state))
        if preempted["flag"] or (args.stop_after
                                 and step + 1 >= args.stop_after):
            print(f"[preemption] stopping at step {step + 1} — "
                  "checkpointing")
            if store:
                store.wait()
                store.save(step + 1, (params, opt_state))
            summary = {"final_loss": losses[-1], "first_loss": losses[0],
                       "steps": len(losses), "stragglers": stragglers,
                       "preempted_at": step + 1}
            print(json.dumps(summary))
            sys.exit(0)
    if store:
        store.wait()
        store.save(args.steps, (params, opt_state))
    summary = {"final_loss": losses[-1] if losses else None,
               "first_loss": losses[0] if losses else None,
               "steps": len(losses), "stragglers": stragglers}
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
