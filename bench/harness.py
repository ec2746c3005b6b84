"""What every cell shares: finding things by name, the device check, the
peaks table, per-layer metric readers and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives:

- configuration ``<c>``: the file that its ``configs`` entry names;
- traffic mix ``<t>``: ``bench/traffic/<t>.json``, read by
  ``bench.generator``; its ``kind`` names the module that runs it,
  ``bench/<kind>_cell.py``;
- per-layer metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read(ctx)``
  returns a number or ``None`` (nothing to read: the metric is left out).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(Exception):
    """The run cannot produce a result (no chip, unknown device, a name
    that nothing answers to)."""


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, bench: Dict, name: str, root: str = ROOT):
        self.root = root
        self.bench = bench
        self.entry = _by_name(bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = _by_name(bench["configs"], self.entry["config"],
                             "configuration")
        with open(os.path.join(root, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic_name = self.entry["traffic"]
        path = os.path.join(root, "bench", "traffic",
                            f"{self.traffic_name}.json")
        with open(path) as f:
            self.traffic = json.load(f)

    def runner(self):
        """The module that runs this cell's kind of traffic."""
        return importlib.import_module(f"bench.{self.traffic['kind']}_cell")

    def _applies(self, metric: Dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    def per_layer(self) -> List[Dict]:
        return [m for m in self.bench["per_layer"] if self._applies(m)]


def load_reader(name: str, root: str = ROOT):
    """The reader module of per-layer metric ``name``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"per-layer metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(cell: Cell, ctx: Dict) -> Dict[str, Dict]:
    """Every per-layer metric of ``cell`` whose reader finds something."""
    out = {}
    for m in cell.per_layer():
        value = load_reader(m["name"], cell.root).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def load_peaks(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        return json.load(f)


def peaks_for(device_kind: str, platform: str, root: str = ROOT) -> Dict:
    """The peaks of ``device_kind``.  A CPU, or a device the table does
    not hold, is an error: no number is ever read against a guess."""
    if platform == "cpu":
        raise BenchError("the benchmark measures an accelerator; JAX found "
                         "only the CPU")
    table = load_peaks(root)["devices"]
    if device_kind not in table:
        raise BenchError(f"device {device_kind!r} is not in "
                         f"bench/peaks.json")
    return table[device_kind]


def check_devices(chips: int, root: str = ROOT):
    """The first ``chips`` accelerator devices and their peaks; raises
    BenchError where JAX finds no accelerator or too few."""
    import jax
    devs = jax.devices()
    peaks = peaks_for(devs[0].device_kind, devs[0].platform, root)
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips], peaks


def device_block(devices, memory_peak_bytes: int,
                 busy_s: Optional[float] = None,
                 window_s: Optional[float] = None) -> Dict:
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}
    if busy_s is not None:
        d["busy_s"] = busy_s
        d["window_s"] = window_s
    return d


def emit(result: Dict, checks: Dict[str, Dict]) -> None:
    """Print the numbers compared beside their limits as the last lines
    on standard error, and the result as the last line of standard
    output, with the checks under the key that comes last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the directory the program
    takes (``JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache``
    in the checkout), holding every program however fast it compiled."""
    import jax
    from repro.common.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
