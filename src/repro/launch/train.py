"""Training launcher CLI.

On a real cluster every host runs this under its own process index with
``jax.distributed.initialize()`` picking up the coordinator from the
environment; on this container ``--fake-devices N`` forces N host devices
so the full mesh/sharding path is exercised.

Examples:
  # tiny smoke run, 1 device
  PYTHONPATH=src python -m repro.launch.train --arch minicpm_2b --reduced \\
      --steps 20

  # sharded run on 8 fake devices (2x4 data x model mesh)
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_32b --reduced \\
      --steps 10 --fake-devices 8 --mesh 2x4
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config of the same family")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--schedule", default="wsd",
                    choices=["wsd", "cosine", "constant"])
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--moe-impl", default="local")
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 (data x model)")
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    from repro.obs import log as obs_log
    obs_log.add_log_args(ap)
    args = ap.parse_args()
    # progress defaults to INFO on stderr (a launcher's progress is not a
    # machine protocol; --quiet silences it)
    log = obs_log.setup_logging("INFO", quiet=args.quiet,
                                verbose=args.verbose)

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax

    from repro.configs import get_config, reduced as reduce_cfg
    from repro.data.pipeline import DataConfig
    from repro.distributed import sharding as shd
    from repro.launch.mesh import batch_axes
    from repro.models import build
    from repro.models.transformer import Runtime
    from repro.train.optimizer import OptimizerConfig, ScheduleConfig
    from repro.train.step import TrainConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = build(cfg)

    mesh = None
    state_sh = None
    rt = Runtime()
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((d, m), ("data", "model"))
        rt = Runtime(mesh=mesh, batch_axes=batch_axes(mesh),
                     moe_impl=args.moe_impl, remat=True)

    tcfg = TrainConfig(
        optimizer=OptimizerConfig(schedule=ScheduleConfig(
            kind=args.schedule, peak_lr=args.lr,
            warmup_steps=max(args.steps // 10, 1),
            total_steps=args.steps)),
        microbatch=args.microbatch,
    )
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    trainer = Trainer(model, tcfg, dcfg,
                      TrainerConfig(steps=args.steps,
                                    ckpt_dir=args.ckpt_dir,
                                    ckpt_every=args.ckpt_every,
                                    log_every=max(args.steps // 10, 1)),
                      rt=rt, mesh=mesh, state_shardings=state_sh)
    state, history = trainer.run(seed=0)
    for h in history:
        log.info("step %5d loss %.4f lr %.2e dt %.0fms stalls %d",
                 h["step"], h["loss"], h["lr"], h["dt"] * 1e3,
                 h["producer_stalls"])
    log.info("done: %d steps; straggler events: %d",
             args.steps, trainer.straggler_events)
    return 0


if __name__ == "__main__":
    sys.exit(main())
