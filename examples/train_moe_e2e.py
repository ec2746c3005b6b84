"""End-to-end driver: train a ~100M-parameter MoE LM for a few hundred
steps with the full Hecate/FSSDP stack (scheduler, re-sharding,
checkpointing, eval).

  PYTHONPATH=src python examples/train_moe_e2e.py                 # full run
  PYTHONPATH=src python examples/train_moe_e2e.py --steps 10      # quick
"""
import argparse
import time

import jax
import numpy as np

from repro.common.config import ModelConfig, MoEConfig, TrainConfig
from repro.checkpoint import store
from repro.core.schedule import ReshardingPolicy
from repro.data.pipeline import make_stream
from repro.launch.inputs import make_runtime
from repro.launch.mesh import make_debug_mesh
from repro.train import step as step_lib
from repro.train.trainer import HecateScheduler, train_loop


def model_100m() -> ModelConfig:
    """~100M-param fine-grained MoE (olmoe-style family, reduced)."""
    return ModelConfig(
        name="moe-100m", arch_type="moe", num_layers=8, d_model=512,
        num_heads=8, num_kv_heads=4, head_dim=64, d_ff=1024,
        vocab_size=32_000,
        moe=MoEConfig(num_experts=16, experts_per_token=4, d_ff=1024,
                      slots_per_device=2),
        act="silu_glu", norm="rms", tie_embeddings=True,
        dtype="float32", remat=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    cfg = model_100m()
    print(f"{cfg.name}: {cfg.param_count()/1e6:.0f}M params "
          f"({cfg.active_param_count()/1e6:.0f}M active/token)")
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=20,
                     total_steps=args.steps)
    stream = make_stream(cfg.vocab_size, args.seq_len, args.global_batch,
                         kind="bytes", seed=0)
    sched = HecateScheduler(cfg, ep=1, impl="ep",
                            resharding=ReshardingPolicy(interval=100))
    t0 = time.time()

    def cb(i, state, metrics):
        if i and i % 100 == 0:
            store.save(args.ckpt_dir, i, {"params": state.params})

    rt = make_runtime(cfg, make_debug_mesh(1, 1), impl="ep")
    state, hist = train_loop(cfg, rt, tc, stream, scheduler=sched,
                             num_steps=args.steps, log_every=10, callback=cb)
    store.save(args.ckpt_dir, args.steps, {"params": state.params})
    dt = time.time() - t0
    toks = args.steps * args.global_batch * args.seq_len
    first = np.mean([h["xent"] for h in hist[:10]])
    last = np.mean([h["xent"] for h in hist[-10:]])
    print(f"\n{args.steps} steps in {dt/60:.1f} min "
          f"({toks/dt:.0f} tokens/s CPU)")
    print(f"xent: {first:.3f} -> {last:.3f}")
    print(f"checkpoint: {store.latest_step(args.ckpt_dir)}")


if __name__ == "__main__":
    main()
