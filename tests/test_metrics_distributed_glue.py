"""Metrics sink + multi-host glue (single-process degradation) tests."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.common.config import TrainConfig
from repro.data.pipeline import make_stream
from repro.launch.distributed import (globalize_batch, host_stream,
                                      process_info)
from repro.models.model import Runtime
from repro.train.metrics import MetricLogger, device_stats, expert_stats
from repro.train.trainer import HecateScheduler, train_loop


def test_expert_stats():
    counts = np.array([[100.0, 100, 100, 100], [400, 0, 0, 0]])
    s = expert_stats(counts)
    assert 0.4 < s["expert_entropy_frac"] < 0.6   # one uniform + one peaked
    assert s["expert_imbalance_max"] == 4.0


def test_device_stats():
    loads = np.array([[10.0, 10, 10, 50]])
    assert device_stats(loads)["device_straggler_factor"] == 2.5


def test_metric_logger_jsonl(tmp_path):
    path = str(tmp_path / "m.jsonl")
    ml = MetricLogger(path)
    rec = ml.log(0, {"loss": jnp.float32(2.0), "time_s": 0.25,
                     "expert_counts": np.ones((2, 4)),
                     "device_loads": np.ones((2, 2))})
    ml.close()
    # the caller's step time survives; the logger keeps no clock of its own
    assert rec["loss"] == 2.0 and rec["time_s"] == 0.25
    assert "tokens_per_s" not in rec
    assert rec["expert_entropy_frac"] > 0.99
    on_disk = [json.loads(l) for l in open(path)]
    assert on_disk[0]["step"] == 0 and on_disk[0]["time_s"] == 0.25


def test_train_loop_with_metric_logger(tmp_path):
    cfg = C.get_smoke("gpt-moe-s")
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=4)
    stream = make_stream(cfg.vocab_size, 16, 4, seed=0)
    sched = HecateScheduler(cfg, ep=1, impl="ep")
    ml = MetricLogger(str(tmp_path / "train.jsonl"))
    state, hist = train_loop(cfg, Runtime(), tc, stream, scheduler=sched,
                             num_steps=4, log_every=0, metric_logger=ml)
    ml.close()
    recs = [json.loads(l) for l in open(tmp_path / "train.jsonl")]
    assert len(recs) == 4
    assert "device_straggler_factor" in recs[0]
    # the loop's time_s (dispatch to read-back) survives the logger, in
    # the history and on disk alike
    for h, r in zip(hist, recs):
        assert h["time_s"] > 0 and r["time_s"] == h["time_s"]
        assert "tokens_per_s" not in r


def test_single_process_glue_degrades():
    info = process_info()
    assert info["process_count"] == 1
    batch = {"tokens": np.zeros((4, 8), np.int32)}
    out = globalize_batch(batch, jax.sharding.SingleDeviceSharding(
        jax.devices()[0]))
    assert out["tokens"].shape == (4, 8)
    it = host_stream(make_stream, vocab_size=100, seq_len=8, global_batch=4)
    assert next(it)["tokens"].shape == (4, 9)
