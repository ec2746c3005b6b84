"""The plain reference against the program at smoke sizes on the CPU.

In float32 the program's first steps, as a run of a training cell
makes them, must agree with the reference to rounding: losses, every
leaf's gradient norm as the optimizer got it, every leaf's change.
The tiny configuration's capacity factor of 1.0 makes experts drop
assignments, so the reference's drop rule is checked too."""
import jax
import pytest

from bench import harness, reference, train_cell
from bench.tests import tiny


@pytest.fixture(scope="module", params=["gpt", "olmoe"])
def readings(request, tmp_path_factory):
    cfg = tiny.CONFIG if request.param == "gpt" else tiny.OLMOE_CONFIG
    root = str(tmp_path_factory.mktemp(request.param))
    cell = harness.Cell(tiny.write(root, config=cfg), tiny.CELL, root=root)
    setup = train_cell.Setup(cell, 2**40 + 3, jax.devices()[:1])
    prog = setup.check_steps()
    setup.state.clear()
    ref = train_cell.reference_readings(
        cfg, setup.opt, train_cell.capacity_of(setup), setup.key,
        prog["batches"])
    return prog, ref


def test_losses_agree(readings):
    prog, ref = readings
    assert prog["losses"] == pytest.approx(ref["losses"], rel=1e-5)


def test_every_gradient_norm_agrees(readings):
    prog, ref = readings
    assert set(prog["grad_norms"]) == set(ref["grad_norms"])
    for k, v in ref["grad_norms"].items():
        assert prog["grad_norms"][k] == pytest.approx(v, rel=1e-4), k


def test_every_change_agrees(readings):
    prog, ref = readings
    for k, v in ref["delta_norms"].items():
        assert prog["delta_norms"][k] == pytest.approx(v, rel=1e-4), k


def test_gaps_are_rounding(readings):
    g = train_cell.gaps(*readings)
    assert g["loss_gap"] < 1e-5 and g["grad_gap"] < 1e-4 \
        and g["delta_gap"] < 1e-4
    assert g["quiet_leaves"] == []


def test_capacity_drops_happen_at_this_size(tmp_path):
    # some expert gets more than its capacity, so the comparison above
    # covers the drop rule
    import numpy as np
    from bench import generator
    cfg, mix = tiny.CONFIG, tiny.MIX
    stream = generator.TopicStream(mix, cfg["vocab_size"], 2**40 + 3)
    cap = reference.capacity(cfg, mix["global_batch"] * mix["seq_len"], 1)
    params = reference.init_params(cfg, train_cell.seed_key(2**40 + 3))
    dropped = []
    for _ in range(train_cell.CHECK_STEPS):
        _, m = reference.loss_fn(params, jax.numpy.asarray(
            stream.next_batch()["tokens"]), cfg, cap)
        dropped.append(float(m["dropped_frac"]))
        assert float(m["dropped_frac"]) == 0 or \
            np.asarray(m["expert_counts"]).max() > cap
    assert max(dropped) > 0


def test_capacity_rule():
    # 1.0 x 256 tokens x 2 / (1 x (4 owned + 2 extra slots)) = 85.33
    assert reference.capacity(tiny.CONFIG, 256, 1) == 86
    # four devices: 1 owned + 2 extra slots each
    assert reference.capacity(tiny.CONFIG, 64, 4) == 11
