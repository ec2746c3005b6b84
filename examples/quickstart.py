"""Quickstart: train a small FSSDP MoE model for 40 steps on CPU.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

import repro.configs as configs
from repro.common.config import TrainConfig
from repro.core.schedule import ReshardingPolicy
from repro.data.pipeline import make_stream
from repro.launch.inputs import make_runtime
from repro.launch.mesh import make_debug_mesh
from repro.train.trainer import HecateScheduler, train_loop


def main():
    cfg = configs.get_smoke("gpt-moe-s")
    print(f"model: {cfg.name} — {cfg.param_count()/1e6:.1f}M params, "
          f"{cfg.moe.num_experts} experts (top-{cfg.moe.experts_per_token})")
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=5, total_steps=40)
    stream = make_stream(cfg.vocab_size, seq_len=64, global_batch=8,
                         kind="bytes")
    # The Hecate control loop: load prediction -> Algorithm 1 plans ->
    # FSSDP step -> feedback; Algorithm 2 re-shards every 20 steps.
    scheduler = HecateScheduler(cfg, ep=1, impl="ep",
                                resharding=ReshardingPolicy(interval=20))
    # a one-device (data, model) mesh runs the sparse FSSDP MoE layer
    rt = make_runtime(cfg, make_debug_mesh(1, 1), impl="ep")
    state, history = train_loop(cfg, rt, tc, stream,
                                scheduler=scheduler, num_steps=40,
                                log_every=5)
    print(f"\nloss: {history[0]['loss']:.3f} -> {history[-1]['loss']:.3f}")
    assert history[-1]["loss"] < history[0]["loss"]
    print("quickstart OK")


if __name__ == "__main__":
    main()
