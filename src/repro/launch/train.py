"""Training launcher.

Runs the FSSDP training loop on a ``(data, model)`` mesh built from the
first ``--mesh-data x --mesh-model`` devices.  The default 1x1 mesh runs
the sparse MoE layer and the Pallas kernels on one chip; on the CPU the
kernels run in interpret mode, and a multi-device run simulates its mesh
on host devices (set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
in the environment).

  PYTHONPATH=src python -m repro.launch.train --arch gpt-moe-s --smoke \
      --steps 50 --impl ring --mesh-data 2 --mesh-model 4
"""
from __future__ import annotations

import argparse
import json
from typing import NamedTuple, Optional


class TrainSetup(NamedTuple):
    """Everything ``train_loop`` takes besides the config."""
    mesh: object
    rt: object
    tc: object
    stream: object
    scheduler: Optional[object]
    supervisor: Optional[object]


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--impl", default="ring",
                    choices=["ring", "a2a", "dense", "ep"])
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="expert-parallel (EP) degree")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--resharding-interval", type=int, default=100)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="crash-safe periodic checkpointing interval "
                         "(atomic + checksummed; 0 = final save only)")
    ap.add_argument("--keep-checkpoints", type=int, default=3,
                    help="keep-last retention for store.gc")
    ap.add_argument("--no-resume", action="store_true",
                    help="do not auto-resume from the newest intact "
                         "checkpoint in --checkpoint-dir")
    ap.add_argument("--no-step-guard", action="store_true",
                    help="disable the non-finite loss/grad skip guard")
    ap.add_argument("--max-bad-steps", type=int, default=3,
                    help="consecutive skipped steps before abort with "
                         "rollback to the last intact checkpoint")
    ap.add_argument("--elastic", action="store_true",
                    help="attach the in-run elastic recovery supervisor: "
                         "device loss shrinks the mesh in-process (roll "
                         "back + replay), cleared faults grow it back, "
                         "stragglers are de-weighted at reshard time "
                         "(requires --mesh-data and --checkpoint-dir)")
    ap.add_argument("--min-ep", type=int, default=1,
                    help="abort instead of shrinking below this EP size")
    ap.add_argument("--step-timeout", type=float, default=0.0,
                    help="wall-clock watchdog: a step slower than this "
                         "(seconds) is treated as a wedged collective "
                         "(0 = disabled; only with --elastic)")
    ap.add_argument("--data", default="synthetic",
                    choices=["synthetic", "bytes"])
    ap.add_argument("--skew", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-json", default="")
    args = ap.parse_args(argv)
    if args.elastic and not args.checkpoint_dir:
        ap.error("--elastic needs --checkpoint-dir (the shrink path "
                 "rolls back to the newest intact checkpoint)")
    return args


def build(cfg, args) -> TrainSetup:
    """The mesh, runtime, data and scheduler ``main`` trains ``cfg`` with
    (``cfg`` is a parameter so that a caller may cut its depth)."""
    from repro.common.config import TrainConfig
    from repro.core.schedule import ReshardingPolicy
    from repro.data.pipeline import make_stream
    from repro.launch import inputs as inp
    from repro.launch.mesh import make_debug_mesh
    from repro.train.trainer import HecateScheduler

    mesh = make_debug_mesh(args.mesh_data, args.mesh_model)
    rt = inp.make_runtime(cfg, mesh, impl=args.impl, use_pallas=True)
    ep = mesh.shape["model"]

    tc = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                     warmup_steps=max(args.steps // 10, 1), seed=args.seed,
                     microbatch=args.microbatch,
                     step_guard=not args.no_step_guard,
                     max_bad_steps=args.max_bad_steps,
                     checkpoint_dir=args.checkpoint_dir,
                     checkpoint_every=args.checkpoint_every,
                     keep_checkpoints=args.keep_checkpoints,
                     auto_resume=not args.no_resume)
    stream = make_stream(cfg.vocab_size, args.seq_len, args.global_batch,
                         kind=args.data, seed=args.seed, skew=args.skew)
    scheduler = None
    if cfg.moe.enabled:
        scheduler = HecateScheduler(
            cfg, ep=ep, impl=args.impl,
            resharding=ReshardingPolicy(interval=args.resharding_interval))

    supervisor = None
    if args.elastic:
        from repro.train.supervisor import TrainSupervisor, surviving_mesh

        def runtime_factory(ep_new):
            return inp.make_runtime(cfg, surviving_mesh(args.mesh_data,
                                                        ep_new),
                                    impl=args.impl, use_pallas=True)

        supervisor = TrainSupervisor(ep=ep,
                                     runtime_factory=runtime_factory,
                                     min_ep=args.min_ep,
                                     step_timeout_s=args.step_timeout)
    return TrainSetup(mesh, rt, tc, stream, scheduler, supervisor)


def main(argv=None):
    args = parse_args(argv)
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()

    import repro.configs as configs
    from repro.train.trainer import save_train_state, train_loop

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    s = build(cfg, args)
    # periodic checkpointing + auto-resume now live INSIDE train_loop
    # (crash-safe: atomic renames, per-array checksums, keep-last GC,
    # resume from the newest intact step — see repro.train.trainer)
    state, history = train_loop(cfg, s.rt, s.tc, s.stream,
                                scheduler=s.scheduler, num_steps=args.steps,
                                supervisor=s.supervisor)
    if args.checkpoint_dir:
        save_train_state(s.tc, int(state.step), state, s.scheduler)
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump(history, f)
    print(f"final loss: {history[-1]['loss']:.4f} "
          f"(start {history[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
