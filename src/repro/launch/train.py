"""QAT training driver.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch bit-bert-base --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
      --devices 4 --mesh 2x2 --steps 100

``--smoke`` selects the reduced config (real weights on this CPU container);
full configs are for real clusters — their step functions are exactly what
the dry-run lowers.  ``--devices N`` requests N host placeholder devices
(set before jax import, hence the env dance at the top).
"""

import argparse
import os


def _parse_early():
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--devices", type=int, default=0)
    args, _ = ap.parse_known_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}"
        )


_parse_early()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.configs import get_config, list_configs  # noqa: E402
from repro.configs.smoke import smoke_variant  # noqa: E402
from repro.data.pipeline import DataConfig, TokenPipeline  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.runtime import fault_tolerance as FT  # noqa: E402
from repro.runtime import train_loop as TL  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list(list_configs()))
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x2")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)

    data, model = (int(x) for x in args.mesh.split("x"))
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data, model)

    tcfg = TL.TrainConfig(
        optimizer=adamw.AdamWConfig(
            lr=args.lr, warmup_steps=max(args.steps // 10, 1), total_steps=args.steps
        ),
        accum_steps=args.accum,
    )
    pipe = TokenPipeline(
        DataConfig(
            vocab_size=cfg.vocab_size,
            seq_len=args.seq,
            global_batch=args.batch,
            seed=args.seed,
            frontend_positions=cfg.encoder.n_positions if cfg.encoder else 0,
            frontend_dim=(cfg.encoder.d_input or cfg.d_model) if cfg.encoder else 0,
        )
    )
    shapes = {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)}
    if cfg.encoder is not None:
        shapes["frontend"] = jax.ShapeDtypeStruct(
            (args.batch, cfg.encoder.n_positions, cfg.encoder.d_input or cfg.d_model),
            jnp.float32,
        )
    step = TL.make_train_step(cfg, tcfg, mesh, shapes)
    params, opt = TL.init_train_state(jax.random.PRNGKey(args.seed), cfg)

    manager = CheckpointManager(args.ckpt_dir or f"/tmp/repro-ckpt-{args.arch}", keep=2)
    runner = FT.TrainingRunner(
        step,
        pipe,
        manager,
        FT.RunnerConfig(
            total_steps=args.steps,
            checkpoint_every=args.ckpt_every,
            log_every=max(args.steps // 20, 1),
        ),
    )
    runner.install_signal_handlers()
    start, params, opt = runner.try_restore(params, opt)
    try:
        params, opt, hist = runner.run(params, opt, start)
    finally:
        runner.restore_signal_handlers()
    if hist:
        first, last = hist[0]["loss"], hist[-1]["loss"]
        print(f"[train] loss {first:.4f} -> {last:.4f} over {args.steps} steps")
    print(f"[train] p50 step {runner.p50*1e3:.0f} ms, p99 {runner.p99*1e3:.0f} ms")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
