"""Production training launcher.

    python -m repro.launch.train --arch qwen3-32b --steps 200 \
        --ckpt-dir /ckpt/run1 [--smoke] [--mesh local|single|multi]

On real hardware --mesh single/multi builds the production mesh; on this
CPU container --smoke --mesh local runs the identical code path (pjit,
sharded state, fault-tolerant supervised loop, async checkpoints) on a
1-device mesh. The loop is deterministic-resumable: state restores from the
latest checkpoint and the data pipeline replays by step index.
"""
from __future__ import annotations

import argparse
import logging
import time

import jax

from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.configs import get_config, list_archs, smoke
from repro.data import SyntheticLM
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.optim import OptConfig
from repro.runtime.monitor import FaultTolerantLoop, HeartbeatMonitor
from repro.sharding.rules import MeshCtx, set_mesh_ctx
from repro.training import make_train_step, train_state_init

log = logging.getLogger("repro.train")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--mesh", choices=["local", "single", "multi"], default="local")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", choices=["cosine", "wsd"], default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--loss-chunks", type=int, default=4)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    # minicpm ships with WSD (arXiv:2404.06395); others default cosine
    schedule = args.schedule or ("wsd" if args.arch.startswith("minicpm") else "cosine")
    opt_cfg = OptConfig(peak_lr=args.lr, warmup=max(5, args.steps // 20),
                        total_steps=args.steps, schedule=schedule)

    mesh = {"local": lambda: make_local_mesh(("data", "model")),
            "single": lambda: make_production_mesh(multi_pod=False),
            "multi": lambda: make_production_mesh(multi_pod=True)}[args.mesh]()
    set_mesh_ctx(MeshCtx(mesh=mesh))

    pipe = SyntheticLM(cfg.vocab_size, batch=args.batch, seq=args.seq, seed=0)
    step_jit = jax.jit(make_train_step(cfg, opt_cfg, loss_chunks=args.loss_chunks),
                       donate_argnums=(0,))

    state = train_state_init(cfg, jax.random.PRNGKey(0))
    start = 0
    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt and latest_step(args.ckpt_dir) is not None:
        start, state = restore_checkpoint(args.ckpt_dir, state)
        log.info("restored checkpoint at step %d", start)

    monitor = HeartbeatMonitor()
    metrics_holder = {}

    def step_fn(st, i):
        st, m = step_jit(st, pipe.batch_at(i))
        if (i + 1) % args.log_every == 0:
            log.info("step %d loss %.4f lr %.2e gnorm %.3f", i + 1,
                     float(m["loss"]), float(m["lr"]), float(m["grad_norm"]))
        metrics_holder["last"] = m
        return st, m

    t0 = time.time()
    if ckpt:
        def restore():
            s = latest_step(args.ckpt_dir)
            _, st = restore_checkpoint(args.ckpt_dir, state)
            return s, st

        loop = FaultTolerantLoop(step_fn, ckpt, ckpt_every=args.ckpt_every,
                                 monitor=monitor)
        state, end = loop.run(state, start, args.steps - start, restore)
    else:
        for i in range(start, args.steps):
            t1 = time.perf_counter()
            state, _ = step_fn(state, i)
            monitor.record(i, time.perf_counter() - t1)
    dt = time.time() - t0
    tokens = (args.steps - start) * args.batch * args.seq
    log.info("done: %.1fs, %.0f tok/s, median step %.3fs, %d stragglers",
             dt, tokens / max(dt, 1e-9), monitor.median, len(monitor.stragglers))


if __name__ == "__main__":
    main()
