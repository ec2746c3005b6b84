"""The trace reduction, on hand-counted intervals and on one training
step recorded on a TPU v5e (gpt-moe-s, 3 layers, batch 8 x 2048)."""
import os

import pytest

from bench import tracereduce as tr

NS = 1e-9
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_gpt_moe_s_train_step.json.gz")


def hand():
    # device 0: a loop [0, 100) enclosing a fusion, a kernel and an
    # all-gather, then a flash call; device 1: one op [0, 50)
    d0 = [("while.1", 0, 100), ("fusion.1", 10, 20),
          ("grouped_mlp_fwd.3", 30, 20), ("all-gather.2", 60, 30),
          ("flash_attention.1", 120, 30)]
    d1 = [("grouped_mlp_fwd.7", 0, 50)]
    host = [("bench.window", 0, 200), ("bench.batch", 100, 20),
            ("$x.py:1 f", 140, 60), ("$y.py:2 g", 160, 10)]
    return tr.Events({"/device:TPU:0": d0, "/device:TPU:1": d1}, host)


def test_busy_and_window():
    ev = hand()
    assert ev.window_s() == pytest.approx(200 * NS)
    # device 0: [0,100) u [120,150) = 130; device 1: 50; mean 90
    assert ev.busy_s() == pytest.approx(90 * NS)


def test_kernel_time_and_calls_by_op_name():
    ev = hand()
    secs, calls = ev.kernel("grouped_mlp_fwd")
    assert secs == pytest.approx((20 + 50) / 2 * NS)
    assert calls == 1.0
    assert ev.kernel("flash_attention") == (pytest.approx(15 * NS), 0.5)
    assert ev.kernel("grouped_mlp_dgrad") == (0.0, 0.0)


def test_exposed_collective_time():
    # the all-gather [60, 90) overlaps no leaf compute op on device 0
    assert hand().exposed_collective_s() == pytest.approx(30 / 2 * NS)


def test_idle_gaps_and_labels():
    ev = hand()
    gaps = ev.idle_gaps("/device:TPU:0")
    assert gaps == [(100, 120), (150, 200)]
    assert ev.label((100, 120)) == "bench.batch"
    # no bench span: the innermost Python frame over the gap's middle
    assert ev.label((150, 200)) == "$x.py:1 f"
    assert ev.label((160, 170)) == "$y.py:2 g"


def test_self_times_subtract_enclosed_ops():
    st = hand().self_times("/device:TPU:0")
    assert st["while.1"] == 100 - 20 - 20 - 30
    assert st["fusion.1"] == 20
    assert st["flash_attention.1"] == 30


def test_breakdown_lists():
    b = hand().breakdown()
    # self time summed over devices, over 2 devices
    assert b["device_ops"][0] == ["grouped_mlp_fwd.7", pytest.approx(
        25 * NS)]
    assert [g[0] for g in b["idle_gaps"]] == ["$x.py:1 f", "bench.batch"]
    assert b["idle_gaps"][0][1] == pytest.approx(50 * NS)


def test_clipped_to_the_window():
    ev = tr.Events({"/device:TPU:0": [("fusion.1", 0, 100)]},
                   [("bench.window", 50, 100)])
    assert ev.busy_s() == pytest.approx(50 * NS)
    assert ev.idle_gaps() == [(100, 150)]


def test_op_name_from_hlo_text():
    assert tr.op_name("%fusion.12 = bf16[8]{0} fusion(%p.1)") == "fusion.12"
    assert tr.matches("grouped_mlp_fwd.3", "grouped_mlp_fwd")
    assert not tr.matches("grouped_mlp_fwd_x.3", "grouped_mlp_fwd")
    assert not tr.matches("fusion.9", "flash_attention")


def test_recorded_v5e_step():
    ev = tr.Events.from_json(RECORDED)
    assert 0.5 < ev.window_s() < 0.7
    assert 0.9 * ev.window_s() < ev.busy_s() < ev.window_s()
    # 3 layers: the forward of each runs twice (remat), the backward once
    assert ev.kernel("grouped_mlp_fwd")[1] == 6
    assert ev.kernel("grouped_mlp_dgrad")[1] == 3
    assert ev.kernel("grouped_mlp_wgrad")[1] == 3
    assert ev.kernel("flash_attention")[1] == 6
    b = ev.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0][0] == "bench.batch"
    total_self = sum(ev.self_times("/device:TPU:0").values()) * NS
    assert total_self == pytest.approx(ev.busy_s(), rel=1e-6)
