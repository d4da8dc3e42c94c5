"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 100 \
      --reduced --batch 8 --seq 128 [--devices 8 --mesh 2x4]

On this CPU container --reduced trains a smoke-sized variant of the chosen
architecture for real (loss goes down); on a TPU fleet the same driver with
the production mesh and full config is the deployment path.  Integrates the
full substrate: placement-aware input pipeline, fault-tolerant runner with
checkpoint/restart, straggler avoidance, optional int8 cross-pod gradient
compression (--grad-compression), MoE expert placement refresh.
"""

from __future__ import annotations

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-sized config (CPU-friendly)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--devices", type=int, default=0,
                    help="virtual host devices (0 = real devices)")
    ap.add_argument("--mesh", type=str, default="",
                    help="'DxM' data x model (default: all devices on data)")
    ap.add_argument("--ckpt-dir", type=str, default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--num-shards", type=int, default=64)
    ap.add_argument("--num-hosts", type=int, default=8)
    ap.add_argument("--inject-failures", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}"
        )
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config, reduce_config
    from repro.data import PlacementAwarePipeline
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.steps import make_train_step
    from repro.models import identity_dispatch, init_params
    from repro.optim.optimizers import make_optimizer
    from repro.parallel import (batch_shardings, param_shardings,
                                set_active_mesh)
    from repro.runtime import FaultTolerantRunner

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg, dtype="float32")

    mesh = None
    if args.mesh:
        d, m = map(int, args.mesh.split("x"))
        mesh = make_debug_mesh(data=d, model=m)
        set_active_mesh(mesh)

    dispatch = None
    if cfg.moe:
        ranks = mesh.shape["model"] if mesh else 1
        dispatch = identity_dispatch(cfg.moe.num_experts, ranks)

    opt = make_optimizer("adamw", args.lr)
    step_fn, _ = make_train_step(cfg, optimizer=opt, moe_dispatch=dispatch,
                                 chunk=max(32, args.seq // 4))
    params = init_params(cfg, jax.random.PRNGKey(0), moe_dispatch=dispatch)
    opt_state = opt.init(params)

    if mesh is not None:
        pshard = param_shardings(jax.eval_shape(lambda: params), mesh)
        oshard = param_shardings(jax.eval_shape(lambda: opt_state), mesh)
        params = jax.device_put(params, pshard)
        opt_state = jax.device_put(opt_state, oshard)
        jit_step = jax.jit(step_fn, in_shardings=(pshard, oshard, None),
                           donate_argnums=(0, 1))
    else:
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    pipeline = PlacementAwarePipeline(
        num_shards=args.num_shards, num_hosts=args.num_hosts,
        vocab_size=cfg.vocab_size, batch_size=args.batch, seq_len=args.seq,
    )
    ckpt = CheckpointManager(args.ckpt_dir, keep=2, async_save=True)

    metrics_log = []

    def run_step(state, batch):
        p, o = state
        dev_batch = {
            "tokens": jnp.asarray(batch["tokens"]),
            "targets": jnp.asarray(batch["targets"]),
        }
        if cfg.frontend:
            dev_batch["frontend"] = jnp.zeros(
                (args.batch, cfg.frontend_len, cfg.d_model), jnp.float32
            )
        p, o, metrics = jit_step(p, o, dev_batch)
        metrics_log.append(float(metrics["loss"]))
        return (p, o), metrics

    runner = FaultTolerantRunner(
        run_step, (params, opt_state), pipeline, ckpt,
        ckpt_every=args.ckpt_every,
    )
    if args.inject_failures:
        runner.kill_input_host(0)

    t0 = time.time()
    result = runner.run(args.steps)
    dt = time.time() - t0
    first = np.mean(metrics_log[:5]) if metrics_log else float("nan")
    last = np.mean(metrics_log[-5:]) if metrics_log else float("nan")
    print(f"steps={result['steps']} restarts={result['restarts']} "
          f"avg_input_span={result['avg_input_span']:.2f} "
          f"idle_hosts={pipeline.idle_host_fraction():.2f}")
    print(f"loss: first5={first:.4f} last5={last:.4f} "
          f"({'improved' if last < first else 'NOT improved'}) "
          f"wall={dt:.1f}s")
    for step, ev in result["events"][:10]:
        print(f"  event@{step}: {ev}")
    return 0 if last < first else 1


if __name__ == "__main__":
    raise SystemExit(main())
