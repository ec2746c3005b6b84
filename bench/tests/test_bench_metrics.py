"""The per-layer readers on the recorded v5e training step: each finds
its number and stays within (0, 100] %; a reader with nothing to read
returns None."""
import json
import os

import numpy as np
import pytest

from bench import harness, tracereduce
from bench.tests.test_bench_tracereduce import RECORDED

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ctx(steps=1):
    with open(os.path.join(ROOT, "bench", "configs", "gpt-moe-s.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           "zipf-topics-drift.json")) as f:
        mix = json.load(f)
    ev = tracereduce.Events.from_json(RECORDED)
    # 3 layers, 64 experts; 32768 assignments a layer, some over capacity
    counts = np.full((3, 64), 512.0)
    counts[:, 0], counts[:, 1] = 1500.0, 12.0
    rec = {"pad_frac": np.float32(0.5), "expert_counts": counts}
    return {"config": cfg, "traffic": mix, "chips": 1, "events": ev,
            "peaks": harness.peaks_for("TPU v5 lite", "tpu"),
            "steps": [rec] * steps, "capacity": 964,
            "tokens_per_s": 16384 / ev.window_s()}


@pytest.mark.parametrize("name", [
    "mfu.train", "idle_share.train", "pad_frac.train",
    "grouped_mlp_roofline.train", "flash_attention_roofline.train"])
def test_reader_on_recorded_step(name):
    value = harness.load_reader(name).read(ctx())
    assert value is not None and 0 < value <= 100, value


def test_mfu_hand_count():
    c = ctx()
    flops = 3 * (3 * (2 * 768 * 3072 + 4 * 12 * 64 * 2049 / 2
                      + 2 * 768 * 64 + 2 * 2 * 768 * 1536 * 2)
                 + 2 * 768 * 50304)
    want = 100 * flops * c["tokens_per_s"] / 197e12
    assert harness.load_reader("mfu.train").read(c) == pytest.approx(want)


def test_readers_without_steps_read_nothing():
    c = ctx(steps=0)
    for name in ("mfu.train", "pad_frac.train",
                 "grouped_mlp_roofline.train"):
        assert harness.load_reader(name).read(c) is None, name


def test_rooflines_read_nothing_without_their_kernel():
    c = ctx()
    c["events"] = tracereduce.Events(
        {"/device:TPU:0": [("fusion.1", 0, 100)]},
        [("bench.window", 0, 200)])
    for name in ("grouped_mlp_roofline.train",
                 "flash_attention_roofline.train"):
        assert harness.load_reader(name).read(c) is None, name
