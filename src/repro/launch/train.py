"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs real training on the available devices (CPU here; the same code path
pjit-shards on a TPU mesh), with the full substrate engaged: synthetic data
pipeline, AdamW + schedule, checkpoint/restart, and optional XiTAO-scheduled
microbatch execution (``--orchestrate``) with PTT straggler telemetry.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from ..checkpointing import CheckpointManager
from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..data import SyntheticLM
from ..models import get_model, make_train_step
from ..optimizer import adamw_init, cosine_schedule
from ..parallel.sharding import use_sharding
from .compile_cache import enable_compile_cache
from .mesh import make_debug_mesh


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--orchestrate", action="store_true",
                    help="run microbatches through the XiTAO scheduler")
    ap.add_argument("--microbatches", type=int, default=2)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    print(f"arch={cfg.name} params={model.param_count():,}")

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch)
    sched = cosine_schedule(args.lr, warmup_steps=max(args.steps // 20, 2),
                            total_steps=args.steps)

    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    start_step = 0

    mgr = None
    if args.checkpoint_dir:
        mgr = CheckpointManager(args.checkpoint_dir)
        if args.resume and mgr.latest() is not None:
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                {"params": params, "opt": opt})
            start_step, tree = mgr.restore(like)
            params, opt = tree["params"], tree["opt"]
            print(f"resumed from step {start_step}")

    if args.orchestrate:
        _train_orchestrated(args, cfg, model, params, opt, data, sched,
                            start_step)
        return

    step_fn = jax.jit(make_train_step(model, lr_schedule=sched),
                      donate_argnums=(0, 1))
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = data.batch(step)
        params, opt, metrics = step_fn(params, opt, batch)
        if step % 5 == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} ({dt:.1f}s)")
        if mgr and (step + 1) % args.checkpoint_every == 0:
            mgr.async_save(step + 1, {"params": params, "opt": opt})
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt})
    print(f"done: {args.steps - start_step} steps in "
          f"{time.time() - t0:.1f}s")


def _train_orchestrated(args, cfg, model, params, opt, data, sched,
                        start_step) -> None:
    """Microbatch DAG through the paper's scheduler (threaded runtime)."""
    from ..core import hikey960, make_policy
    from ..core.train_orchestrator import run_training_threaded
    from ..optimizer import adamw_update

    grad_j = jax.jit(jax.value_and_grad(
        lambda p, b: model.loss(p, b)[0]))

    def grad_fn(p, b):
        loss, g = grad_j(p, b)
        return g, {"loss": loss}

    upd_j = jax.jit(lambda p, g, o: adamw_update(p, g, o, lr=args.lr))

    batches = []
    for s in range(start_step, args.steps):
        full = data.batch(s)
        mb = args.microbatches
        bs = full["tokens"].shape[0] // mb
        batches.append([
            {k: v[i * bs:(i + 1) * bs] for k, v in full.items()}
            for i in range(mb)])

    stats = run_training_threaded(
        hikey960(), make_policy("molding:crit-ptt"), params, opt,
        grad_fn, lambda p, g, o: upd_j(p, g, o), batches)
    print(f"orchestrated: {stats['completed']} TAOs in "
          f"{stats['elapsed_s']:.1f}s; last losses "
          f"{[round(l, 3) for l in stats['losses'][-3:]]}")


if __name__ == "__main__":
    main()
