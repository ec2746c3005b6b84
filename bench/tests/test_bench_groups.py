"""A four-chip training cell against the reference, on four CPU devices.

The tiny configuration with ``slots_per_device: 0`` gives the program no
replicas, so what it drops follows GShard's group rule: each device
keeps the first ``capacity`` of an expert's assignments among the
tokens it holds.  Its first steps, as a run of a four-chip cell makes
them (``train_cell.Setup.check_steps``), must agree with the reference
under that rule to float32 rounding, with drops happening; the
one-group rule must fail the same readings.  The reference laid out
over the four devices must agree with the reference on one.

Everything runs in one subprocess, since the device count is fixed
when JAX starts."""
import json
import os
import subprocess
import sys

import pytest

from bench import train_cell
from bench.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**40 + 3

SCRIPT = r"""
import json, sys, tempfile
import jax
from bench import harness, reference, train_cell
from bench.tests import tiny

cfg = dict(tiny.CONFIG, moe=dict(tiny.CONFIG["moe"], slots_per_device=0))
root = tempfile.mkdtemp()
bench = tiny.write(root, config=cfg)
bench["workloads"][0]["chips"] = 4
cell = harness.Cell(bench, tiny.CELL, root=root)
devs = jax.devices()[:4]
setup = train_cell.Setup(cell, int(sys.argv[1]), devs)
prog = setup.check_steps()
setup.state.clear()
cap = train_cell.capacity_of(setup)
batches = prog["batches"]
prog.pop("batches")


def readings(groups, devices):
    return train_cell.reference_readings(cfg, setup.opt, cap, setup.key,
                                         batches, groups=groups,
                                         devices=devices)


params = reference.init_params(cfg, setup.key)
dropped = [float(reference.loss_fn(params, jax.numpy.asarray(b), cfg, cap,
                                   groups=4)[1]["dropped_frac"])
           for b in batches]
specs = {k: [None if a is None else str(a) for a in s.spec]
         for k, s in train_cell.reference_shardings(cfg, devs).items()}
print(json.dumps({"cap": cap, "program": prog,
                  "grouped": readings(4, devs),
                  "one_group": readings(1, devs),
                  "grouped_one_device": readings(4, devs[:1]),
                  "dropped": dropped, "specs": specs}))
"""


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(SEED)], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_four_chip_cell_agrees_with_grouped_reference(four):
    g = train_cell.gaps(four["program"], four["grouped"])
    assert g["loss_gap"] < 1e-5 and g["grad_gap"] < 1e-4 \
        and g["delta_gap"] < 1e-4, g


def test_drops_happen_in_every_check_step(four):
    # capacity factor 1.0: 64 tokens x 2 / 4 experts = 32 = the cap, so
    # any skew drops
    assert four["cap"] == 32
    assert min(four["dropped"]) > 0, four["dropped"]


def test_one_group_rule_fails_the_same_readings(four):
    g = train_cell.gaps(four["program"], four["one_group"])
    limits = tiny.CONFIG["limits"]["train"]
    assert g["grad_gap"] > limits["grad_gap"], g
    assert train_cell.judge(g, limits)[1] is False


@pytest.mark.parametrize("what", ["losses", "grad_norms", "delta_norms"])
def test_sharded_reference_equals_one_device(four, what):
    one, sharded = four["grouped_one_device"][what], four["grouped"][what]
    if what == "losses":
        assert sharded == pytest.approx(one, rel=1e-6)
        return
    assert set(sharded) == set(one)
    for k, v in one.items():
        assert sharded[k] == pytest.approx(v, rel=1e-5), k


def test_reference_leaves_are_split_over_the_devices(four):
    specs = four["specs"]
    # experts, heads and vocabulary split; norm scales replicated
    assert specs["wi"] == [None, "ref", None, None]
    assert specs["wo_e"] == [None, "ref", None, None]
    assert specs["router"] == [None, None, "ref"]
    assert specs["wq"] == [None, None, "ref", None]
    assert specs["wo"] == [None, "ref", None, None]
    assert specs["embed"] == ["ref", None]
    assert specs["ln1"] == [None, None]
    assert specs["final_norm"] == [None]

