"""The program's own trace spans: the tiny training loop run under the
profiler on the CPU, read back through ``bench.spans``, the span
reductions on hand-made events, and the recorded v5e steps."""
import gzip
import json
import os
import shutil
import tempfile

import jax
import pytest

from bench import harness, scopes, spans, trace_report
from bench import tracereduce as tr
from bench.tests.test_bench_metrics import ctx as recorded_ctx
from bench.tests.test_bench_tracereduce import RECORDED

MS = 1e6                        # ns in a ms
# two steps of gpt-moe-s.train.zipf-topics-drift traced on a TPU v5e
# (bench/trace_report.py --fixture), with the scope of each operation
SPANS = os.path.join(os.path.dirname(__file__), "data",
                     "v5e_gpt_moe_s_train_spans.json.gz")
# the loop's phases of one step, in the order the loop enters them
PHASES = ("hecate.batch", "hecate.upload", "hecate.reshard", "hecate.plan",
          "hecate.dispatch", "hecate.plan_ahead", "hecate.readback",
          "hecate.observe")


@pytest.fixture(scope="module")
def traced_loop():
    """Four steps of the smoke GPT-MoE on a 1x1 mesh (ring, plan-ahead
    on), traced; returns the Events."""
    import repro.configs as configs
    from repro.launch import train as train_launch
    from repro.train.trainer import train_loop
    cfg = configs.get_smoke("gpt-moe-s")
    s = train_launch.build(cfg, train_launch.parse_args([
        "--arch", "gpt-moe-s", "--smoke", "--steps", "4",
        "--global-batch", "2", "--seq-len", "32"]))
    trace_dir = tempfile.mkdtemp(prefix="spans_")
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            train_loop(cfg, s.rt, s.tc, s.stream, scheduler=s.scheduler,
                       num_steps=4, log_every=0)
    finally:
        jax.profiler.stop_trace()
    try:
        yield spans.load_dir(trace_dir, 0)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def test_loop_spans_nest_in_their_step_in_order(traced_loop):
    ev = traced_loop
    steps = ev.steps()
    assert [st[spans.STEP_SPAN][0][2]["step_num"] for st in steps] == \
        [1, 2, 3, 4]
    for k, st in enumerate(steps, start=1):
        want = [p for p in PHASES if p != "hecate.plan_ahead" or k < 4]
        assert all(len(st[p]) == 1 for p in want), (k, sorted(st))
        starts = [st[p][0][0] for p in want]
        assert starts == sorted(starts), k
        plan0, plan1, args = st["hecate.plan"][0]
        assert args["source"] == ("sync" if k == 1 else "prefetch")
        child = "hecate.plan.alg1" if k == 1 else "hecate.plan.wait"
        s, e, _ = st[child][0]
        assert plan0 <= s <= e <= plan1
        s, e, _ = st["hecate.plan.to_device"][0]
        assert plan0 <= s <= e <= plan1
    # every span of the loop lies inside one of its steps
    inside = sum(len(v) for st in steps for v in st.values())
    assert inside == len(ev.loop_spans())


def test_worker_spans_carry_the_step_they_plan(traced_loop):
    ev = traced_loop
    worker = {}
    for name, s, d, args in ev.spans:
        if name.startswith(spans.WORKER_PREFIX):
            worker.setdefault(name, {})[args["step"]] = (s, s + d)
    assert sorted(worker["hecate.worker.alg1"]) == [2, 3, 4]
    assert sorted(worker["hecate.worker.tables"]) == [2, 3, 4]
    # a prefetched plan was made before its step joined it
    for st in ev.steps()[1:]:
        k = st[spans.STEP_SPAN][0][2]["step_num"]
        assert worker["hecate.worker.alg1"][k][1] \
            <= st["hecate.plan.wait"][0][1]


def hand():
    """Two steps on one device, times in ms: the device idles over
    [0, 70) (first plan), [500, 600) (observe, then the next plan) and
    [990, 1000) (the last observe)."""
    def sp(name, s, e, **args):
        return (name, s * MS, (e - s) * MS, args)
    loop = [
        sp("hecate.step", 0, 550, step_num=1),
        sp("hecate.batch", 0, 10), sp("hecate.upload", 10, 20),
        sp("hecate.reshard", 20, 25),
        sp("hecate.plan", 25, 60, source="prefetch"),
        sp("hecate.plan.wait", 26, 40), sp("hecate.plan.to_device", 40, 60),
        sp("hecate.dispatch", 60, 70), sp("hecate.plan_ahead", 70, 80),
        sp("hecate.worker.alg1", 75, 95, step=2),
        sp("hecate.readback", 80, 500), sp("hecate.observe", 500, 540),
        sp("hecate.calibrate", 505, 535),
        sp("hecate.step", 550, 1000, step_num=2),
        sp("hecate.batch", 550, 560), sp("hecate.upload", 560, 570),
        sp("hecate.reshard", 570, 575),
        sp("hecate.plan", 575, 590, source="sync"),
        sp("hecate.plan.alg1", 576, 585), sp("hecate.plan.tables", 585, 588),
        sp("hecate.plan.to_device", 588, 590),
        sp("hecate.dispatch", 590, 600), sp("hecate.readback", 600, 990),
        sp("hecate.observe", 990, 995),
    ]
    ops = [("fusion.1", 70, 100), ("fusion.2", 170, 100),
           ("fusion.3", 270, 100), ("fusion.4", 370, 50),
           ("fusion.5", 420, 80), ("fusion.6", 600, 100),
           ("fusion.7", 700, 100), ("fusion.8", 800, 50),
           ("fusion.9", 850, 140)]
    return spans.Traced({"/device:TPU:0": [(n, s * MS, d * MS)
                                           for n, s, d in ops]},
                        [(tr.WINDOW_SPAN, 0, 1000 * MS)], loop)


SMAP = {"fusion.1": "gate.fwd", "fusion.2": "dispatch.bwd",
        "fusion.3": "spag.fwd", "fusion.4": "spag.bwd",
        "fusion.5": "optimizer.fwd", "fusion.7": "combine.remat",
        "fusion.8": "sprs.bwd", "fusion.9": "optimizer.fwd"}


def test_steps_and_idle_by_span():
    ev = hand()
    steps = ev.steps()
    assert len(steps) == 2 and "hecate.worker.alg1" not in steps[0]
    assert steps[1]["hecate.plan"][0][2] == {"source": "sync"}
    assert ev.idle_gaps() == [(0, 70 * MS), (500 * MS, 600 * MS),
                              (990 * MS, 1000 * MS)]
    # each instant of idle time goes to the innermost span over it; the
    # second gap crosses ten of them, and the loop's bookkeeping between
    # phases stays under hecate.step
    want_ms = {"hecate.batch": 20, "hecate.upload": 20,
               "hecate.reshard": 10, "hecate.plan": 2,
               "hecate.plan.wait": 14, "hecate.plan.alg1": 9,
               "hecate.plan.tables": 3, "hecate.plan.to_device": 22,
               "hecate.dispatch": 20, "hecate.observe": 15,
               "hecate.calibrate": 30, "hecate.step": 15}
    got = ev.idle_by_span()
    assert got == {k: pytest.approx(v / 1e3) for k, v in want_ms.items()}
    assert sum(got.values()) == pytest.approx(0.180)
    cov = ev.span_coverage()
    assert cov["idle"] == pytest.approx(165 / 180)
    assert cov["step_host"] == pytest.approx(985 / 1000)


def test_idle_outside_every_span():
    ev = spans.Traced({"/device:TPU:0": [("fusion.1", 10 * MS, 80 * MS)]},
                      [(tr.WINDOW_SPAN, 0, 100 * MS)])
    assert ev.idle_by_span() == {spans.UNCOVERED: pytest.approx(0.020)}
    assert ev.span_coverage() == {"idle": 0.0, "step_host": None}
    assert ev.steps() == []


def layers(ev, smap):
    """The five per-step quantities of ``trace_report``'s ``layers``
    line over a window of two steps."""
    ms = scopes.device_ms(scopes.op_ms(ev, 2), smap)
    out = {f"{k}_ms": scopes.scope_sum(ms, *v) if smap else None
           for k, v in scopes.LAYERS.items()}
    out["host_gap_ms"] = ev.host_gap_ms()
    out["scheduler_ms"] = ev.scheduler_ms()[0]
    out["sprs_ms"] = scopes.sprs_ms(ms) if smap else None
    return out


@pytest.mark.parametrize("name,want", [
    ("host_gap_ms", 90.0),
    ("scheduler_ms", (10 + 50 + 45) / 2),
    ("dispatch_ms", (100 + 100 + 100) / 2),
    ("materialize_ms", (100 + 50 + 50) / 2),
    ("optimizer_ms", (80 + 140) / 2),
])
def test_layers_on_hand_made_events(name, want):
    assert layers(hand(), SMAP)[name] == pytest.approx(want)


def test_materialize_splits_spag_and_sprs():
    got = layers(hand(), SMAP)
    assert got["sprs_ms"] == pytest.approx(50.0)
    assert got["materialize_ms"] - got["sprs_ms"] == pytest.approx(50.0)


def test_scheduler_split_by_child_and_source():
    total, split = hand().scheduler_ms()
    assert total == pytest.approx(52.5)
    assert split["hecate.plan.wait"] == pytest.approx(7.0)
    assert split["hecate.calibrate"] == pytest.approx(15.0)
    assert split["plan_source"] == {"prefetch": 1, "sync": 1}


@pytest.mark.parametrize("name", [
    "host_gap_ms", "scheduler_ms", "dispatch_ms", "materialize_ms",
    "optimizer_ms"])
def test_layers_read_nothing_without_spans_or_scopes(name):
    ev = hand()
    ev.spans = []
    assert layers(ev, {})[name] is None


def recorded():
    ev = spans.Traced.from_json(SPANS)
    with gzip.open(SPANS, "rt") as f:
        data = json.load(f)
    n = data["steps"]
    return ev, scopes.device_ms(scopes.op_ms(ev, n), data["scopes"]), \
        data["scopes"]


@pytest.mark.parametrize("name,lo,hi", [
    ("host_gap_ms", 7.0, 8.5),
    ("scheduler_ms", 4.5, 6.0),
    ("dispatch_ms", 50.0, 60.0),
    ("materialize_ms", 85.0, 95.0),
    ("optimizer_ms", 24.0, 27.0),
])
def test_layers_on_recorded_v5e_steps(name, lo, hi):
    ev, _, smap = recorded()
    assert lo < layers(ev, smap)[name] < hi


def test_recorded_v5e_steps_are_covered_by_spans_and_scopes():
    ev, ms, _ = recorded()
    assert 0.55 < sum(ms.values()) / 1e3 < 0.57         # a step's busy s
    assert ms[scopes.UNSCOPED] < 0.1 * sum(ms.values())
    assert ev.span_coverage()["idle"] > 0.9
    idle = ev.idle_by_span()
    assert sum(idle.values()) == pytest.approx(ev.window_s() - ev.busy_s())
    # the device waits longest while the host reads the metrics back
    assert max(idle, key=idle.get) == "hecate.readback"


def test_trace_report_lines_on_recorded_v5e_steps(capsys):
    ev, _, smap = recorded()
    trace_report.report(ev, {}, smap, 2, {"plan_ahead_hits": 2})
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["phase"] for x in lines] == [
        "scope_ms", "idle_by_span", "span_coverage", "layers", "window"]
    got = lines[3]
    assert got["host_gap_ms"] == layers(ev, smap)["host_gap_ms"]
    assert got["scheduler_counters"] == {"plan_ahead_hits": 2}
    assert got["scheduler_split"]["plan_source"] == {"prefetch": 2}
    assert lines[4]["scoped_share"] > 0.9


# the five readers and the breakdown on the recorded step as the benchmark
# first computed them (PERF.md section 5): the program's own spans, which
# a trace now carries too, change none of them
RECORDED_VALUES = {
    "mfu.train": 5.638725989344248,
    "idle_share.train": 2.86313653531427,
    "pad_frac.train": 50.0,
    "grouped_mlp_roofline.train": 7.621196913208727,
    "flash_attention_roofline.train": 2.5206163808009574,
}
RECORDED_BREAKDOWN = {
    "device_ops": [
        ["grouped_mlp_wgrad.13", 0.04263509],
        ["dynamic-update-slice.871", 0.024595257],
        ["grouped_mlp_wgrad.12", 0.021313762],
        ["flash_attention.27", 0.020946523],
        ["flash_attention.28", 0.020220311],
        ["fusion.1216", 0.019902276],
        ["grouped_mlp_dgrad.13", 0.015146712],
        ["broadcast_select_fusion.37", 0.013943295],
        ["grouped_mlp_fwd.28", 0.013387986],
        ["select_add_fusion.5", 0.013264228]],
    "idle_gaps": [
        ["bench.batch", 0.016381363],
        ["$array.py:631 _value", 5.858e-06],
        ["$array.py:631 _value", 2.368e-06],
        ["$array.py:631 _value", 1.286e-06],
        ["$array.py:631 _value", 1.021e-06],
        ["$array.py:631 _value", 1.012e-06],
        ["$array.py:631 _value", 7.04e-07],
        ["$array.py:631 _value", 7.01e-07],
        ["$array.py:631 _value", 6.38e-07],
        ["$array.py:631 _value", 5.96e-07]],
}


@pytest.mark.parametrize("name", sorted(RECORDED_VALUES))
def test_recorded_step_reads_as_before(name):
    assert harness.load_reader(name).read(recorded_ctx()) == \
        RECORDED_VALUES[name]


def test_recorded_step_breakdown_as_before():
    ev = spans.Traced.from_json(RECORDED)
    assert ev.spans == []
    assert ev.breakdown() == RECORDED_BREAKDOWN
    assert tr.Events.from_json(RECORDED).breakdown() == RECORDED_BREAKDOWN
