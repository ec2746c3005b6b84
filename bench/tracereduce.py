"""Reduction of a profiler trace to the numbers the benchmark reports.

``Events`` holds, per device, the operations that ran on it as
(name, start_ns, duration_ns), and the host spans the benchmark
annotated (``bench.*``).  The traced window is the host span
``bench.window``; every interval is clipped to it.  From these it
computes:

- busy time: the union of a device's operation intervals;
- a kernel's device time and number of calls (operations named
  ``<kernel>`` or ``<kernel>.<n>``);
- exposed collective time: the part of a device's collective operations
  (all-gather, reduce-scatter, collective-permute, all-to-all,
  all-reduce) during which no other operation runs on it;
- the breakdown: the operations with the most self time (a loop's time
  less its body's), and the longest idle gaps of the first device, each
  labelled with the host span that overlaps it most, else the innermost
  Python frame under it.

Device numbers are means over the devices traced.  ``Events`` loads from
the profiler's ``.xplane.pb`` (``from_xplane``) or from the small JSON
form a test keeps (``to_json`` / ``from_json``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
COLLECTIVES = ("all-gather", "reduce-scatter", "collective-permute",
               "all-to-all", "all-reduce")
WINDOW_SPAN = "bench.window"
UNLABELLED = "host outside the benchmark's spans"
# a TPU trace names an operation by its HLO text, "%name = shape op(...)"
_OP_NAME = re.compile(r"%?([^\s=]+)")


def op_name(text: str) -> str:
    """``fusion.12`` from ``%fusion.12 = bf16[...] fusion(...)``."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text


def matches(op: str, kernel: str) -> bool:
    """Is operation ``op`` (``grouped_mlp_fwd.3``) a call of ``kernel``?"""
    return op == kernel or op.startswith(kernel + ".")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the merged intervals ``a`` not covered by ``b``."""
    b = union(b)
    out = []
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def _clip(iv: Interval, w: Interval) -> Optional[Interval]:
    s, e = max(iv[0], w[0]), min(iv[1], w[1])
    return (s, e) if e > s else None


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


class Events:
    def __init__(self, devices: Dict[str, List[Tuple[str, float, float]]],
                 host: List[Tuple[str, float, float]]):
        self.devices = devices
        self.host = host
        spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
        if spans:
            self.window = spans[0]
        else:
            ops = [(s, s + d) for ev in devices.values() for _, s, d in ev]
            self.window = (min(s for s, _ in ops), max(e for _, e in ops))

    # ---------------------------------------------------------- loading
    @classmethod
    def from_xplane(cls, path: str, n_devices: int) -> "Events":
        """Device planes ``/device:TPU:<i>`` (their ``XLA Ops`` line, where
        a loop's operation encloses its body's) for the first
        ``n_devices`` devices, and the host planes' ``bench.*`` spans and
        Python frames (``$file:line function``)."""
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        devices, host = {}, []
        for plane in pd.planes:
            m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            if m and int(m.group(1)) < n_devices:
                ops = []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops += [(op_name(e.name), e.start_ns, e.duration_ns)
                                for e in line.events]
                devices[plane.name] = ops
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host += [(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(("bench.", "$"))]
        if len(devices) != n_devices:
            raise ValueError(f"trace holds {sorted(devices)}, expected "
                             f"{n_devices} TPU devices")
        return cls(devices, host)

    def to_json(self, path: str) -> None:
        data = {"devices": self.devices, "host": self.host}
        with gzip.open(path, "wt") as f:
            json.dump(data, f)

    @classmethod
    def from_json(cls, path: str) -> "Events":
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        return cls({k: [tuple(e) for e in v]
                    for k, v in data["devices"].items()},
                   [tuple(e) for e in data["host"]])

    # ------------------------------------------------------- intervals
    def _ops(self, dev: str, pred=None) -> List[Interval]:
        out = []
        for name, s, d in self.devices[dev]:
            if pred is None or pred(name):
                iv = _clip((s, s + d), self.window)
                if iv:
                    out.append(iv)
        return out

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, mean over devices."""
        return sum(total(union(self._ops(d))) for d in self.devices) \
            / len(self.devices) / 1e9

    def kernel(self, name: str) -> Tuple[float, float]:
        """(device seconds, calls) of the calls of kernel ``name``, each a
        mean over devices."""
        secs = calls = 0.0
        for d in self.devices:
            ivs = self._ops(d, lambda n: matches(n, name))
            secs += sum(e - s for s, e in ivs)
            calls += len(ivs)
        n = len(self.devices)
        return secs / n / 1e9, calls / n

    def _leaves(self, dev: str) -> List[Tuple[str, float, float]]:
        """The operations of ``dev`` that enclose no other (a loop's
        operation encloses its body)."""
        evs = sorted(self.devices[dev], key=lambda e: (e[1], -e[2]))
        out = []
        for i, (n, s, d) in enumerate(evs):
            nxt = evs[i + 1] if i + 1 < len(evs) else None
            if nxt is None or nxt[1] >= s + d:
                out.append((n, s, d))
        return out

    def exposed_collective_s(self) -> float:
        """Collective time with no other operation running, mean over
        devices."""
        out = 0.0
        for d in self.devices:
            leaves = self._leaves(d)
            coll = [(s, s + du) for n, s, du in leaves if is_collective(n)]
            comp = [(s, s + du) for n, s, du in leaves
                    if not is_collective(n)]
            w = self.window
            coll = [iv for iv in (_clip(c, w) for c in coll) if iv]
            out += total(subtract(coll, comp))
        return out / len(self.devices) / 1e9

    def idle_gaps(self, dev: Optional[str] = None) -> List[Interval]:
        dev = dev or sorted(self.devices)[0]
        return subtract([self.window], self._ops(dev))

    def label(self, gap: Interval) -> str:
        """What the host was doing in ``gap``: the ``bench.*`` span (other
        than the window) that overlaps it most, else the innermost Python
        frame that covers its middle."""
        best, best_ov = None, 0.0
        mid, frame, frame_len = (gap[0] + gap[1]) / 2, None, None
        for name, s, d in self.host:
            if name == WINDOW_SPAN:
                continue
            if name.startswith("bench."):
                ov = min(gap[1], s + d) - max(gap[0], s)
                if ov > best_ov:
                    best, best_ov = name, ov
            elif s <= mid <= s + d and (frame_len is None or d < frame_len):
                frame, frame_len = name, d
        return best or frame or UNLABELLED

    def self_times(self, dev: str) -> Dict[str, float]:
        """Per operation name, device time not covered by an operation
        it encloses (a loop's time less its body's), in the window."""
        evs = sorted(((s, s + d, n) for n, s, d in self.devices[dev]),
                     key=lambda e: (e[0], -e[1]))
        out: Dict[str, float] = {}
        stack: List[list] = []          # [end, name, self time]

        def close(top):
            out[top[1]] = out.get(top[1], 0.0) + top[2]
        for s, e, n in evs:
            iv = _clip((s, e), self.window)
            while stack and stack[-1][0] <= s:
                close(stack.pop())
            if iv is None:
                continue
            if stack:
                stack[-1][2] -= iv[1] - iv[0]
            stack.append([e, n, iv[1] - iv[0]])
        while stack:
            close(stack.pop())
        return out

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The operations with the most self time (mean over devices) and
        the first device's longest idle gaps, labelled."""
        per_op: Dict[str, float] = {}
        for d in self.devices:
            for name, t in self.self_times(d).items():
                per_op[name] = per_op.get(name, 0.0) + t
        n = len(self.devices)
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
                "idle_gaps": [[self.label(g), (g[1] - g[0]) / 1e9]
                              for g in gaps]}


def load_dir(trace_dir: str, n_devices: int) -> Events:
    """The one ``.xplane.pb`` that a ``jax.profiler`` trace wrote."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one xplane.pb under {trace_dir}, "
                         f"found {paths}")
    return Events.from_xplane(paths[0], n_devices)
