"""A run of a training cell with its timed path broken underneath comes
out not correct.  The look for a chip is skipped (the run is driven
past ``harness.check_devices``) and the rest of a run happens at the
tiny CPU size: one test per fault a one-chip training cell can have."""
import time

import jax
import pytest

from bench import harness, train_cell
from bench.tests import tiny

SEED = 2**33 + 5


def _run(tmp_path, monkeypatch, breaker=None):
    root = str(tmp_path)
    cell = harness.Cell(tiny.write(root), tiny.CELL, root=root)
    if breaker is not None:
        orig = train_cell.compile_step
        monkeypatch.setattr(train_cell, "compile_step",
                            lambda *a: breaker(orig, *a))
    result, checks = train_cell.run(
        cell, devices=jax.devices()[:1], peaks=None, seed=SEED,
        seconds=1.0, trace=False, t0=time.perf_counter())
    return result, checks


def test_sound_run_is_correct(tmp_path, monkeypatch):
    result, checks = _run(tmp_path, monkeypatch)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def unchanged_state(orig, pcfg, s, state, batch, pa):
    """A step that returns its state unchanged."""
    step, nbytes, kernels = orig(pcfg, s, state, batch, pa)

    def broken(st, b, p):
        _, metrics = step(st, b, p)
        return st, metrics
    return broken, nbytes, kernels


def half_batch(orig, pcfg, s, state, batch, pa):
    """A step that leaves half the batch out, the mean over the rest."""
    rows = batch["tokens"].shape[0] // 2
    half = {"tokens": jax.ShapeDtypeStruct(
        (rows,) + batch["tokens"].shape[1:], batch["tokens"].dtype)}
    step, nbytes, kernels = orig(pcfg, s, state, half, pa)

    def broken(st, b, p):
        return step(st, {"tokens": b["tokens"][:rows]}, p)
    return broken, nbytes, kernels


@pytest.mark.parametrize("breaker", [unchanged_state, half_batch],
                         ids=["unchanged_state", "half_batch"])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, breaker):
    result, checks = _run(tmp_path, monkeypatch, breaker)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in checks.values())
