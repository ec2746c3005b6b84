"""A tiny benchmark tree for CPU tests: one configuration at the
program's smoke sizes, one training mix, one cell."""
import json
import os

CONFIG = {
    "source": "test", "program_arch": "gpt-moe-s", "num_layers": 2,
    "d_model": 128, "num_heads": 4, "num_kv_heads": 4, "head_dim": 32,
    "vocab_size": 512, "act": "gelu", "norm": "ln", "tie_embeddings": True,
    "rope_theta": 10000.0, "dtype": "float32", "param_dtype": "float32",
    "moe": {"num_experts": 4, "experts_per_token": 2, "d_ff": 256,
            "capacity_factor": 1.0, "slots_per_device": 2,
            "aux_loss_weight": 0.01, "router_z_loss_weight": 0.001},
    "optimizer": {"learning_rate": 0.0003, "weight_decay": 0.1,
                  "beta1": 0.9, "beta2": 0.95, "eps": 1e-08,
                  "grad_clip": 1.0},
    "limits": {"train": {"loss_gap": 1e-3, "grad_gap": 1e-2,
                         "delta_gap": 1e-2}},
}
# the tiny configuration in the cell's bfloat16, with limits set from
# CPU readings of seeds 3-8 (largest of the program / smallest of the fp8
# control / smallest of the half batch): loss_gap 4.2e-5 / 6.4e-5 /
# 5.2e-3, grad_gap 2.5e-3 / 9.3e-3 / 0.25, delta_gap 7.7e-4 / 1.4e-3 /
# 2.6e-2.  Only grad_gap separates the control at this size.
BF16_CONFIG = dict(CONFIG, dtype="bfloat16",
                   limits={"train": {"loss_gap": 1e-3, "grad_gap": 5e-3,
                                     "delta_gap": 5e-3}})
MIX = {"kind": "train", "global_batch": 2, "seq_len": 128, "topics": 4,
       "token_zipf": 1.0, "topic_zipf": 1.0, "drift_every": 2,
       "job_steps": 100, "impl": "ring"}
CELL = "tiny.train.mix"


def write(root, config=CONFIG, mix=MIX, extra_metrics=()):
    """Write the tree under ``root``; returns the BENCHMARK dict."""
    for d in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "bench", "traffic", "mix.json"), "w") as f:
        json.dump(mix, f)
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": CELL, "config": "tiny", "traffic": "mix",
                       "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.03, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": list(extra_metrics),
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench

# olmoe-1b-7b at the program's smoke sizes: SwiGLU experts, RMSNorm,
# untied embeddings, the backward re-gather of the expert chunks
OLMOE_CONFIG = dict(
    CONFIG, program_arch="olmoe-1b-7b", d_model=256, head_dim=64,
    act="silu_glu", norm="rms", tie_embeddings=False,
    moe=dict(CONFIG["moe"], d_ff=256))
