"""What the configuration file states reaches the program, and every
parameter leaf is compared, or the run fails loudly.

- A file key that the program's ``ModelConfig`` or ``MoEConfig`` has no
  field for raises BenchError naming it; an optional key the program
  has is passed through.
- ``gaps`` raises BenchError for a program leaf with no reference
  counterpart, or the reverse; so does a reference leaf that
  ``LEAF_MAP`` does not name.
- QK-norm's reference leaves land on the program's
  ``blocks/l0/attn/{q,k}_norm/scale``."""
import dataclasses

import jax
import pytest

from bench import harness, reference, train_cell
from bench.tests import tiny


def _readings(leaves):
    return {"losses": [1.0, 1.0, 1.0],
            "grad_norms": {k: 1.0 for k in leaves},
            "delta_norms": {k: 1.0 for k in leaves}}


LEAVES = ("embed/embedding", "router", "moe_buffer")


def test_matching_leaves_are_compared():
    g = train_cell.gaps(_readings(LEAVES), _readings(LEAVES))
    assert g["grad_gap"] == 0 and g["delta_gap"] == 0


@pytest.mark.parametrize("side", ["program", "reference"])
def test_a_leaf_on_one_side_only_raises(side):
    more = _readings(LEAVES + ("blocks/l0/attn/q_norm/scale",))
    prog, ref = ((more, _readings(LEAVES)) if side == "program"
                 else (_readings(LEAVES), more))
    with pytest.raises(harness.BenchError, match="q_norm"):
        train_cell.gaps(prog, ref)


def test_a_reference_leaf_with_no_program_leaf_raises():
    with pytest.raises(harness.BenchError, match="extra"):
        train_cell._group({"embed": 1.0, "extra": 4.0})


@pytest.mark.parametrize("key,value", [("qk_norm", True), ("norm_eps", 1e-5),
                                       ("moe.norm_topk_prob", False)])
def test_an_option_reaches_the_program_or_raises(key, value):
    name = key.split(".")[-1]
    if key.startswith("moe."):
        cfg = dict(tiny.CONFIG, moe=dict(tiny.CONFIG["moe"], **{name: value}))
    else:
        cfg = dict(tiny.CONFIG, **{name: value})
    import repro.configs as configs
    preset = configs.get(tiny.CONFIG["program_arch"])
    holder = preset.moe if key.startswith("moe.") else preset
    if name in {f.name for f in dataclasses.fields(holder)}:
        pcfg = train_cell.program_config(cfg)
        got = pcfg.moe if key.startswith("moe.") else pcfg
        assert getattr(got, name) == value
    else:
        with pytest.raises(harness.BenchError, match=name):
            train_cell.program_config(cfg)


def test_an_unknown_moe_key_raises():
    cfg = dict(tiny.CONFIG, moe=dict(tiny.CONFIG["moe"], no_such_knob=1))
    with pytest.raises(harness.BenchError, match="moe.no_such_knob"):
        train_cell.program_config(cfg)


def test_options_absent_leave_the_program_config_as_before():
    pcfg = train_cell.program_config(tiny.CONFIG)
    for k in train_cell.PROGRAM_KEYS:
        assert getattr(pcfg, k) == tiny.CONFIG[k], k


def test_qk_norm_leaves_map_to_the_program_names():
    cfg = dict(tiny.OLMOE_CONFIG, qk_norm=True)
    canon = reference.init_params(cfg, jax.random.PRNGKey(0))
    pcfg = train_cell.program_config(tiny.OLMOE_CONFIG)
    names = set(train_cell.leaf_norms(
        train_cell.program_params(pcfg, canon, 1)))
    assert {"blocks/l0/attn/q_norm/scale",
            "blocks/l0/attn/k_norm/scale"} <= names
    assert names == set(train_cell.LEAF_MAP) - (
        {"embed/unembed"} if cfg["tie_embeddings"] else set())
    grouped = train_cell._group({k: 1.0 for k in canon})
    assert set(grouped) == names
