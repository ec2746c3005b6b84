"""The peaks table: keyed by device kind, with its source; a CPU and an
unknown device are refused."""
import pytest

from bench import harness


def test_table_has_v5e_and_a_source():
    table = harness.load_peaks()
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9


def test_v5e_is_found():
    assert harness.peaks_for("TPU v5 lite", "tpu")["bf16_flops"] == 197e12


def test_unknown_device_is_refused():
    with pytest.raises(harness.BenchError, match="not in"):
        harness.peaks_for("TPU v9 imaginary", "tpu")


def test_cpu_is_refused():
    with pytest.raises(harness.BenchError, match="CPU"):
        harness.peaks_for("cpu", "cpu")


def test_check_devices_refuses_the_cpu():
    # the test suite runs on the CPU: the harness's look for a chip fails
    with pytest.raises(harness.BenchError):
        harness.check_devices(1)
