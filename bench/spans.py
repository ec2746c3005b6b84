"""The program's own host spans in a profiler trace, and what they say of
a traced training window.

The training loop and its scheduler (``repro.train.trainer``) annotate
their work with ``jax.profiler`` spans named ``hecate.*``: one
``hecate.step`` per iteration with a span per phase inside it, the
scheduler's children of ``hecate.plan`` and ``hecate.observe``, and the
plan-ahead worker's ``hecate.worker.*`` spans, which carry the step they
plan for.  ``tracereduce.Events`` keeps the benchmark's spans and Python
frames; ``Traced`` is the same events with the program's spans kept
apart, in ``spans``, each with its annotation arguments, and reads:

- ``steps``: each ``hecate.step`` of the window with the loop spans
  inside it;
- ``idle_by_span``: the device's idle time by the innermost loop span
  over each instant of it;
- ``span_coverage``: the shares of the idle time and of the steps' host
  time that the loop's phase spans cover;
- ``host_gap_ms``: host ms between one step's read-back and the next
  step's dispatch;
- ``scheduler_ms``: host ms a step in the scheduler's spans, with the
  split by child span and plan source.

The worker's spans run on another thread and own no idle time.
"""
from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

from bench import tracereduce as tr

# (name, start_ns, duration_ns, annotation arguments)
Span = Tuple[str, float, float, Dict]
SPAN_PREFIX = "hecate."
STEP_SPAN = "hecate.step"
WORKER_PREFIX = "hecate.worker."
UNCOVERED = "outside the loop's spans"
# the loop's phases that the scheduler's work runs in, and their children
SCHEDULER_PHASES = ("hecate.reshard", "hecate.plan", "hecate.observe")
SCHEDULER_CHILDREN = ("hecate.plan.wait", "hecate.plan.alg1",
                      "hecate.plan.tables", "hecate.plan.to_device",
                      "hecate.calibrate")


def _args(event) -> Dict:
    """A trace event's annotation arguments.  Their type is built on first
    use and warns that it has no module: under an "error" warnings filter
    that warning aborts the process, so it is ignored here."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return {k: v for k, v in event.stats}


def read_spans(path: str) -> List[Span]:
    """The ``hecate.*`` events of the host planes of an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.duration_ns, _args(e))
                        for e in line.events
                        if e.name.startswith(SPAN_PREFIX)]
    return out


class Traced(tr.Events):
    def __init__(self, devices, host, spans: Sequence[Span] = ()):
        super().__init__(devices, host)
        self.spans = list(spans)

    # ---------------------------------------------------------- loading
    @classmethod
    def from_xplane(cls, path: str, n_devices: int) -> "Traced":
        ev = tr.Events.from_xplane(path, n_devices)
        return cls(ev.devices, ev.host, read_spans(path))

    def to_json(self, path: str, **extra) -> None:
        """The events, the spans and ``extra`` keys in one gzip JSON."""
        data = {"devices": self.devices, "host": self.host,
                "spans": self.spans, **extra}
        with gzip.open(path, "wt") as f:
            json.dump(data, f)

    @classmethod
    def from_json(cls, path: str) -> "Traced":
        """A file of ``to_json``; one that holds no spans, as the
        benchmark's own fixtures, reads with none."""
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        return cls({k: [tuple(e) for e in v]
                    for k, v in data["devices"].items()},
                   [tuple(e) for e in data["host"]],
                   [tuple(e) for e in data.get("spans", ())])

    # ------------------------------------------------------- reductions
    def loop_spans(self) -> List[Span]:
        """The training loop's spans (not the plan-ahead worker's) that
        overlap the window, in order of start."""
        return sorted((sp for sp in self.spans
                       if not sp[0].startswith(WORKER_PREFIX)
                       and tr._clip((sp[1], sp[1] + sp[2]), self.window)),
                      key=lambda sp: sp[1])

    def steps(self) -> List[Dict[str, List[Tuple[float, float, Dict]]]]:
        """Each ``hecate.step`` that starts in the window, as {span name:
        [(start, end, arguments)]} of the loop spans inside it, its own
        under ``hecate.step``."""
        loop = self.loop_spans()
        out = []
        for name, s, d, _ in loop:
            if name != STEP_SPAN or not self.window[0] <= s < self.window[1]:
                continue
            kids: Dict[str, list] = {}
            for n2, s2, d2, a2 in loop:
                if s <= s2 and s2 + d2 <= s + d:
                    kids.setdefault(n2, []).append((s2, s2 + d2, a2))
            out.append(kids)
        return out

    def idle_by_span(self) -> Dict[str, float]:
        """Seconds of the first device's idle time by the innermost loop
        span over each instant of it (``hecate.step`` alone: the loop's
        bookkeeping between phases), or UNCOVERED outside every loop
        span.  A gap between two steps crosses several phases, so each
        gets its part."""
        spans = [(s, s + d, n) for n, s, d, _ in self.loop_spans()]
        cuts = sorted({t for s, e, _ in spans for t in (s, e)})
        pieces = []                     # (start, end, innermost span)
        for a, b in zip(cuts, cuts[1:]):
            over = [(e - s, n) for s, e, n in spans if s <= a and b <= e]
            if over:
                pieces.append((a, b, min(over)[1]))
        starts = [a for a, _, _ in pieces]
        out: Dict[str, float] = {}
        for g0, g1 in self.idle_gaps():
            rest = g1 - g0
            for j in range(max(bisect.bisect_right(starts, g0) - 1, 0),
                           len(pieces)):
                a, b, n = pieces[j]
                if a >= g1:
                    break
                ov = min(b, g1) - max(a, g0)
                if ov > 0:
                    out[n] = out.get(n, 0.0) + ov / 1e9
                    rest -= ov
            if rest > 0:
                out[UNCOVERED] = out.get(UNCOVERED, 0.0) + rest / 1e9
        return out

    def span_coverage(self) -> Dict[str, Optional[float]]:
        """Shares covered by the loop's phase spans (every loop span but
        ``hecate.step``): of the first device's idle time, and of the
        host time of the window's steps.  None where there is nothing to
        cover."""
        phases = tr.union([(s, s + d) for n, s, d, _ in self.loop_spans()
                           if n != STEP_SPAN])
        gaps = self.idle_gaps()
        idle = tr.total(gaps)
        steps = [st[STEP_SPAN][0][:2] for st in self.steps()]
        step_t = tr.total(steps)
        in_steps = step_t - sum(tr.total(tr.subtract([st], phases))
                                for st in steps)
        return {"idle": (idle - tr.total(tr.subtract(gaps, phases))) / idle
                if idle > 0 else None,
                "step_host": in_steps / step_t if step_t > 0 else None}

    def dispatched_steps(self) -> List[Dict]:
        """The ``steps`` that reached their dispatch (the window's last
        iteration ends in its batch when the window closes)."""
        return [st for st in self.steps() if "hecate.dispatch" in st]

    def host_gap_ms(self) -> Optional[float]:
        """Mean over consecutive steps of the start of step i+1's
        ``hecate.dispatch`` less the end of step i's ``hecate.readback``,
        in ms; None without such a pair.  The device idles over this
        gap, and also over the part of the read-back that follows the
        step's last operation, which ``idle_by_span`` puts under
        ``hecate.readback``."""
        steps = self.dispatched_steps()
        gaps = [b["hecate.dispatch"][0][0] - a["hecate.readback"][-1][1]
                for a, b in zip(steps, steps[1:])
                if "hecate.readback" in a]
        return 1e-6 * sum(gaps) / len(gaps) if gaps else None

    def scheduler_ms(self) -> Tuple[Optional[float], Dict]:
        """Host ms a step in the scheduler's phases
        (``SCHEDULER_PHASES``), mean over the dispatched steps, and the
        split: ms a step by phase and child span, and how often each
        plan source was taken.  (None, {}) where no step has them."""
        steps = self.dispatched_steps()
        if not any(n in st for st in steps for n in SCHEDULER_PHASES):
            return None, {}

        def ms(name):
            return 1e-6 * sum(e - s for st in steps
                              for s, e, _ in st.get(name, ())) / len(steps)
        sources = collections.Counter(
            a.get("source") for st in steps
            for _, _, a in st.get("hecate.plan", ()))
        split = {n: ms(n) for n in SCHEDULER_PHASES + SCHEDULER_CHILDREN}
        split["plan_source"] = dict(sources)
        return sum(ms(n) for n in SCHEDULER_PHASES), split


def load_dir(trace_dir: str, n_devices: int) -> Traced:
    """The one ``.xplane.pb`` that a ``jax.profiler`` trace wrote."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one xplane.pb under {trace_dir}, "
                         f"found {paths}")
    return Traced.from_xplane(paths[0], n_devices)
