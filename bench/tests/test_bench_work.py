"""Work counters against hand counts at the program's smoke sizes."""
import pytest

from bench import work
from bench.tests import tiny

GPT = tiny.CONFIG                      # gpt-moe-s smoke: gelu, 2 matrices
OLMOE = dict(tiny.CONFIG, act="silu_glu", d_model=256, head_dim=64,
             moe=dict(tiny.CONFIG["moe"], d_ff=256))


def test_model_flops_per_token_gpt_smoke():
    # d 128, 4 heads of 32, 4 kv heads, E 4 top-2 of d_ff 256, V 512,
    # 2 layers, seq 128
    proj = 2 * 128 * (2 * 4 * 32 + 2 * 4 * 32)          # q, o, k, v
    scores = 2 * 2 * 4 * 32 * 129 / 2                    # causal mean
    router = 2 * 128 * 4
    experts = 2 * 2 * 128 * 256 * 2                      # top-2, 2 mats
    head = 2 * 128 * 512
    want = 3 * (2 * (proj + scores + router + experts) + head)
    assert work.model_flops_per_token(GPT, 128) == pytest.approx(want)
    assert want == 3 * (2 * (131072 + 33024 + 1024 + 262144) + 131072)


def test_model_flops_per_token_glu_counts_three_matrices():
    a = work.model_flops_per_token(OLMOE, 128)
    b = work.model_flops_per_token(dict(OLMOE, act="gelu"), 128)
    assert a - b == pytest.approx(3 * 2 * 2 * 2 * 256 * 256)


@pytest.mark.parametrize("kind,flops,nbytes", [
    # 100 rows, 3 experts used, d 128, f 256, 2 matrices
    ("fwd", 2 * 100 * 128 * 256 * 2, 2 * (2 * 128 * 256 * 3 + 2 * 100 * 128)),
    ("dgrad", 2 * 100 * 128 * 256 * 2,
     2 * (2 * 128 * 256 * 3 + 3 * 100 * 128)),
    ("wgrad", 2 * 100 * 128 * 256 * 2,
     2 * 2 * 100 * 128 + 4 * 2 * 128 * 256 * 3),
])
def test_grouped_mlp_hand_counts(kind, flops, nbytes):
    w = work.grouped_mlp(GPT, 100, 3, kind)
    assert w == {"flops": flops, "bytes": nbytes}


def test_flash_attention_hand_count():
    # batch 2, seq 4, 1 head of 8: causal pairs 4*5/2 = 10
    w = work.flash_attention_fwd(2, 4, 1, 8)
    assert w["flops"] == 2 * 2 * 2 * 1 * 8 * 10
    assert w["bytes"] == 2 * 4 * 2 * 4 * 1 * 8


def test_paged_decode_attention_hand_count():
    # lengths 5 and 16 on pages of 4: 2 + 4 pages; 4 heads over 2 kv heads
    w = work.paged_decode_attention([5, 16], 4, heads=4, kv_heads=2,
                                    head_dim=8)
    assert w["bytes"] == 2 * 2 * 6 * 4 * 2 * 8 + 2 * 2 * 2 * 4 * 8
    assert w["flops"] == 2 * 2 * 4 * 8 * (5 + 16)


def test_least_seconds_and_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds({"flops": 200.0, "bytes": 10.0}, peaks) == 2.0
    assert work.bound({"flops": 200.0, "bytes": 10.0}, peaks) == "compute"
    assert work.least_seconds({"flops": 100.0, "bytes": 50.0}, peaks) == 5.0
    assert work.bound({"flops": 100.0, "bytes": 50.0}, peaks) == "memory"
