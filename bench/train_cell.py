"""Training cells: FSSDP training through the repository's own launcher
and ``train_loop``.

Set-up builds one object: the launcher's mesh, runtime and
``HecateScheduler`` (``repro.launch.train.build``), the train step
compiled ahead of time (``jit_train_step(...).lower(...).compile()``, so
that its ``memory_analysis()`` is read), and the state made on the
device from the seed in one jitted call.  It drives that object through
``CHECK_STEPS`` steps of ``train_loop`` on the cell's own traffic, which
compiles and warms everything the window runs, and records what the
correctness check compares.  The window then hands the same state,
step, scheduler and stream to ``train_loop`` again and trains until
``--seconds`` have passed.  Once the window has closed and the state is
freed, the plain reference (``bench.reference``) trains from the same
weights on the same batches, and the program's first ``CHECK_STEPS``
steps are compared with it:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: the worst leaf's gap between the program's gradient norm
  as the optimizer got it (its first moment after one step, over
  ``1 - beta1``) and the reference's clipped gradient norm;
- ``delta_gap``: the worst leaf's gap between the norms of the
  parameters' change over the ``CHECK_STEPS`` steps, leaving out leaves
  whose reference gradient is under a thousandth of the median leaf's.

A gap is measured against the reference's norm of that leaf or of the
median leaf, whichever is larger.  Every program leaf has its reference
counterpart (``LEAF_MAP``) and the reverse, or the check raises.  The
limits are in the configuration file, under ``limits.train``.

The reference drops by GShard's group rule, one group per chip, since
the program shards the flat tokens over the cell's chips in order, and
runs over those chips: its weights, moments and gradients split on
their expert, head or vocabulary axis (``SPLIT_AXIS``).
"""
from __future__ import annotations

import json
import shutil
import statistics
import tempfile
import time
from typing import Dict, List

import numpy as np

from bench import generator, harness, reference, tracereduce

CHECK_STEPS = 3
# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: left out of delta_gap
QUIET_LEAF = 1e-3

# program parameter leaf -> the reference leaves that hold the same
# numbers (the chunk buffer holds every expert of every layer)
LEAF_MAP = {
    "embed/embedding": ("embed",),
    "embed/unembed": ("unembed",),
    "blocks/l0/ln1/scale": ("ln1",),
    "blocks/l0/attn/wq": ("wq",),
    "blocks/l0/attn/wk": ("wk",),
    "blocks/l0/attn/wv": ("wv",),
    "blocks/l0/attn/wo": ("wo",),
    "blocks/l0/attn/q_norm/scale": ("q_norm",),
    "blocks/l0/attn/k_norm/scale": ("k_norm",),
    "blocks/l0/ln2/scale": ("ln2",),
    "final_norm/scale": ("final_norm",),
    "router": ("router",),
    "moe_buffer": ("wi", "wg", "wo_e"),
}


# the configuration file's keys that set the program's ModelConfig, and
# those passed only where the file gives them (each defaults to the
# program's behaviour before it had the field; ``moe`` passes whole)
PROGRAM_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
                "head_dim", "vocab_size", "act", "norm", "tie_embeddings",
                "rope_theta", "dtype", "param_dtype")
OPTIONAL_KEYS = ("qk_norm", "norm_eps")
# the axis each reference leaf is split on over the cell's chips: its
# experts, heads or vocabulary.  A leaf not named here (a norm scale) is
# replicated, and so is one whose axis the chips do not divide.  Split on
# d_model, the partitioner shards the activations on it too, and the
# TPU compiler fails a consistency check on the experts' combine.
SPLIT_AXIS = {"router": 2, "wi": 1, "wg": 1, "wo_e": 1,
              "wq": 2, "wk": 2, "wv": 2, "wo": 1,
              "embed": 0, "unembed": 1}


class WindowClosed(Exception):
    """Raised by the feed when the measured window is over."""


def seed_key(seed: int):
    """A PRNG key from any whole number (beyond 32 bits too)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


# ------------------------------------------------------------- the program
def _require_fields(obj, keys, prefix: str):
    import dataclasses
    have = {f.name for f in dataclasses.fields(obj)}
    for k in keys:
        if k not in have:
            raise harness.BenchError(
                f"the configuration file sets {prefix}{k}, and the "
                f"program's {type(obj).__name__} has no field {k!r}")


def program_config(cfg: Dict):
    """The program's ModelConfig as the configuration file ``cfg`` states
    it: the program's own preset ``program_arch`` with every size and
    setting the file gives.  A setting the program has no field for
    raises BenchError."""
    import dataclasses
    import repro.configs as configs
    pcfg = configs.get(cfg["program_arch"])
    keys = PROGRAM_KEYS + tuple(k for k in OPTIONAL_KEYS if k in cfg)
    _require_fields(pcfg, keys, "")
    _require_fields(pcfg.moe, cfg["moe"], "moe.")
    return pcfg.replace(
        moe=dataclasses.replace(pcfg.moe, **cfg["moe"]),
        **{k: cfg[k] for k in keys})


def program_params(pcfg, canon: Dict, ep: int) -> Dict:
    """The reference's weights laid out as the program's parameters:
    layers stacked on the superblock axis, every (layer, expert) FFN
    packed into its row of the chunk buffer."""
    import jax.numpy as jnp
    from repro.core import moe as moe_core
    from repro.core.placement import homogeneous_sharding
    L, E = canon["router"].shape[0], canon["router"].shape[2]
    mats = [canon["wi"]] + ([canon["wg"]] if "wg" in canon else []) \
        + [canon["wo_e"]]
    chunks = jnp.concatenate([m.reshape(L, E, -1) for m in mats], -1)
    rows = homogeneous_sharding(L, E, ep).global_rows().reshape(-1)
    buf = jnp.zeros((moe_core.buffer_rows(pcfg, ep), chunks.shape[-1]),
                    jnp.float32).at[rows].set(chunks.reshape(L * E, -1))
    embed = {"embedding": canon["embed"]}
    if "unembed" in canon:
        embed["unembed"] = canon["unembed"]
    attn = {k: canon[k] for k in ("wq", "wk", "wv", "wo")}
    for k in ("q_norm", "k_norm"):
        if k in canon:
            attn[k] = {"scale": canon[k]}
    return {
        "embed": embed,
        "blocks": {"l0": {
            "ln1": {"scale": canon["ln1"]},
            "attn": attn,
            "ln2": {"scale": canon["ln2"]}}},
        "final_norm": {"scale": canon["final_norm"]},
        "router": canon["router"],
        "moe_buffer": buf,
    }


def program_state(pcfg, cfg: Dict, key, ep: int):
    """A fresh TrainState holding the seed's weights (jit this)."""
    import jax
    import jax.numpy as jnp
    from repro.optim.adamw import OptState
    from repro.train.step import TrainState
    params = program_params(pcfg, reference.init_params(cfg, key), ep)
    zeros = jax.tree.map(jnp.zeros_like, params)
    return TrainState(params=params,
                      opt=OptState(mu=zeros, nu=jax.tree.map(jnp.zeros_like,
                                                             params),
                                   count=jnp.zeros((), jnp.int32)),
                      step=jnp.zeros((), jnp.int32))


def leaf_norms(tree) -> Dict:
    """{"a/b/c": l2 norm} over the leaves of a dict tree (jit this)."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(k.key) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in flat}


def compile_step(pcfg, s, state, batch, pa):
    """The train step ``train_loop`` runs, compiled ahead of time.
    Returns (step, compiled program's bytes per device, kernels)."""
    from repro.kernels.ops import compiled_kernels
    from repro.train.trainer import jit_train_step
    compiled = jit_train_step(pcfg, s.rt, s.tc).lower(state, batch,
                                                      pa).compile()
    ma = compiled.memory_analysis()
    nbytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
              + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return compiled, int(nbytes), sorted(compiled_kernels(
        compiled.as_text()))


def _span(name, fn):
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


class Feed:
    """The cell's traffic as ``train_loop`` pulls it.  It keeps the
    batches it hands out while ``kept`` is a list, and closes the window
    at ``deadline`` by raising WindowClosed when asked for one more."""

    def __init__(self, stream):
        self.stream = stream
        self.kept = None
        self.deadline = None
        self.closed_at = None

    def __iter__(self):
        return self

    def __next__(self):
        if self.deadline is not None and time.perf_counter() >= self.deadline:
            self.closed_at = time.perf_counter()
            raise WindowClosed
        import jax
        with jax.profiler.TraceAnnotation("bench.batch"):
            batch = self.stream.next_batch()
        if self.kept is not None:
            self.kept.append(batch["tokens"])
        return batch


class Setup:
    """The object set-up builds and the window trains."""

    def __init__(self, cell, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from repro.launch import inputs as inp
        from repro.launch import train as train_launch
        from repro.train import step as step_lib

        t = time.perf_counter()
        self.phases = {}                # set-up phase -> seconds
        cfg, mix = cell.config, cell.traffic
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.chips = cell.chips
        self.pcfg = program_config(cfg)
        b, s_len = mix["global_batch"], mix["seq_len"]
        args = train_launch.parse_args([
            "--arch", cfg["program_arch"], "--steps", str(mix["job_steps"]),
            "--global-batch", str(b), "--seq-len", str(s_len),
            "--impl", mix["impl"], "--mesh-model", str(cell.chips),
            "--seed", str(seed)])
        self.s = train_launch.build(self.pcfg, args)
        self.opt = dict(cfg["optimizer"],
                        warmup_steps=self.s.tc.warmup_steps,
                        total_steps=self.s.tc.total_steps)
        for k, v in cfg["optimizer"].items():
            if getattr(self.s.tc, k) != v:
                raise harness.BenchError(f"launcher sets {k}="
                                         f"{getattr(self.s.tc, k)}, the "
                                         f"file says {v}")
        ep = self.s.mesh.shape["model"]
        self.ep = ep
        self.key = seed_key(seed)
        shardings = step_lib.state_shardings(self.pcfg, self.s.mesh)
        self.make_state = jax.jit(
            lambda k: program_state(self.pcfg, cfg, k, ep),
            out_shardings=shardings)
        state = jax.block_until_ready(self.make_state(self.key))
        self.phases["build_and_weights"] = time.perf_counter() - t
        t = time.perf_counter()
        batch = {"tokens": jax.ShapeDtypeStruct((b, s_len + 1), jnp.int32)}
        pa = inp.concrete_plan(self.pcfg, ep, mix["impl"])
        self.step, self.program_bytes, self.kernels = compile_step(
            self.pcfg, self.s, state, batch, pa)
        self.phases["compile_step"] = time.perf_counter() - t
        self.norms = jax.jit(leaf_norms)
        self.delta_norms = jax.jit(
            lambda p, k: leaf_norms(jax.tree.map(
                lambda a, b: a - b, p,
                program_params(self.pcfg, reference.init_params(cfg, k),
                               ep))))
        sch = self.s.scheduler
        sch.plan_arrays = _span("bench.plan", sch.plan_arrays)
        sch.maybe_reshard = _span("bench.reshard", sch.maybe_reshard)
        sch.observe = _span("bench.observe", sch.observe)
        self.feed = Feed(generator.TopicStream(mix, cfg["vocab_size"], seed))
        self.state = [state]
        del state

    def train(self, num_steps: int, callback):
        """``train_loop`` on the held state; the state it returns is held
        again (a second reference would keep two states on the chip)."""
        from repro.train.trainer import train_loop
        state, _ = train_loop(
            self.pcfg, self.s.rt, self.s.tc, self.feed,
            scheduler=self.s.scheduler,
            train_step_fn=_span("bench.dispatch", self.step),
            state=self.state.pop(), num_steps=num_steps, log_every=0,
            callback=_span("bench.callback", callback))
        self.state.append(state)

    def check_steps(self) -> Dict:
        """The first CHECK_STEPS steps, with what the check compares."""
        got = {"losses": [], "grad_norms": None, "delta_norms": None,
               "mu_scale": 1.0 / (1.0 - self.opt["beta1"])}
        self.feed.kept = []
        t = time.perf_counter()

        def cb(i, state, metrics):
            got["losses"].append(float(metrics["loss"]))
            if i == 0:
                got["grad_norms"] = {
                    k: float(v) * got["mu_scale"]
                    for k, v in self.norms(state.opt.mu).items()}
            if i == CHECK_STEPS - 1:
                got["delta_norms"] = {
                    k: float(v) for k, v in
                    self.delta_norms(state.params, self.key).items()}
        self.train(CHECK_STEPS, cb)
        self.phases["check_steps"] = time.perf_counter() - t
        got["batches"] = self.feed.kept
        self.feed.kept = None
        return got


def run_window(setup: Setup, seconds: float, trace_dir=None) -> Dict:
    """Train until ``seconds`` have passed; returns what the window saw."""
    import jax
    steps: List[Dict] = []

    def cb(i, state, metrics):
        steps.append({k: np.asarray(metrics[k]) for k in
                      ("loss", "step_ok", "pad_frac", "dropped_frac",
                       "expert_counts") if k in metrics})

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    setup.feed.deadline = t0 + seconds
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            setup.train(10 ** 9, cb)
    except WindowClosed:
        pass
    t1 = setup.feed.closed_at
    setup.feed.deadline = None
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return {"steps": steps, "seconds": t1 - t0}


# -------------------------------------------------------------- the check
def reference_shardings(cfg: Dict, devices) -> Dict:
    """Each reference leaf's layout over a one-axis mesh of ``devices``:
    split on its ``SPLIT_AXIS`` where the devices divide it, else
    replicated."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("ref",))
    n = len(devices)
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    out = {}
    for name, leaf in shapes.items():
        spec = [None] * leaf.ndim
        axis = SPLIT_AXIS.get(name)
        if axis is not None and leaf.shape[axis] % n == 0:
            spec[axis] = "ref"
        out[name] = NamedSharding(mesh, P(*spec))
    return out


def reference_readings(cfg: Dict, opt: Dict, cap: int, key, batches,
                       quant=None, groups: int = 1, devices=None) -> Dict:
    """The reference's losses, clipped first-step gradient norms and
    change norms over ``len(batches)`` steps, keyed as the program's
    leaves.  ``cap`` assignments are kept per (group, expert); the
    reference runs over ``devices`` (the default device when None)."""
    import jax
    import jax.numpy as jnp
    shard = reference_shardings(cfg, devices or jax.devices()[:1])
    step = jax.jit(reference.make_train_step(cfg, opt, cap, quant, groups),
                   in_shardings=(shard, shard, shard, None, None),
                   out_shardings=(shard, shard, shard, None, shard),
                   donate_argnums=(0, 1, 2))
    init = jax.jit(lambda k: reference.init_params(cfg, k),
                   out_shardings=shard)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shard)
    params = init(key)
    mu, nu = zeros(params), zeros(params)
    sq = jax.jit(lambda t: {k: jnp.sum(jnp.square(v)) for k, v in t.items()})
    losses, grads_sq = [], None
    for i, toks in enumerate(batches):
        params, mu, nu, loss, grads = step(params, mu, nu, i,
                                           jnp.asarray(toks))
        losses.append(float(loss))
        if i == 0:
            grads_sq = {k: float(v) for k, v in sq(grads).items()}
        del grads
    delta_sq = {k: float(v) for k, v in jax.jit(
        lambda p, k: sq(jax.tree.map(lambda a, b: a - b, p, init(k))))(
            params, key).items()}
    del params, mu, nu
    return {"losses": losses, "grad_norms": _group(grads_sq),
            "delta_norms": _group(delta_sq)}


def _group(sq: Dict) -> Dict:
    """Reference leaves' squared norms -> program leaves' norms."""
    mapped = {k for keys in LEAF_MAP.values() for k in keys}
    if set(sq) - mapped:
        raise harness.BenchError(f"reference leaves with no program leaf: "
                                 f"{sorted(set(sq) - mapped)}")
    out = {}
    for leaf, keys in LEAF_MAP.items():
        have = [sq[k] for k in keys if k in sq]
        if have:
            out[leaf] = float(np.sqrt(sum(have)))
    return out


def gaps(prog: Dict, ref: Dict) -> Dict:
    """The three numbers compared, from the program's (or a stand-in's)
    readings and the reference's.  A leaf on one side only raises
    BenchError: no leaf goes unchecked."""
    for what in ("grad_norms", "delta_norms"):
        mine, theirs = set(prog[what]), set(ref[what])
        if mine != theirs:
            raise harness.BenchError(
                f"{what}: program leaves with no reference counterpart "
                f"{sorted(mine - theirs)}, reference leaves with no "
                f"program counterpart {sorted(theirs - mine)}")
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(prog["losses"], ref["losses"]))
    gref = ref["grad_norms"]
    med_g = statistics.median(gref.values())
    quiet = {k for k, v in gref.items() if v < QUIET_LEAF * med_g}

    def worst(p, r, skip=()):
        names = [k for k in r if k not in skip]
        med = statistics.median(r[k] for k in names)
        return max(abs(p[k] - r[k]) / max(r[k], med) for k in names)

    return {"loss_gap": loss_gap,
            "grad_gap": worst(prog["grad_norms"], gref),
            "delta_gap": worst(prog["delta_norms"], ref["delta_norms"],
                               quiet),
            "quiet_leaves": sorted(quiet)}


def judge(g: Dict, limits: Dict):
    """The numbers compared, each beside its limit from the configuration
    file's ``limits.train``, and whether all of them hold.  A number the
    file gives no limit is not compared."""
    checks = {k: {"value": g[k], "limit": v} for k, v in limits.items()}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return checks, bool(correct)


def capacity_of(setup: Setup) -> int:
    """The program's capacity, per (device pair, compute slot), which is
    the reference's per (group, expert) with one group per chip."""
    mix = setup.mix
    return reference.capacity(setup.cfg, mix["global_batch"]
                              * mix["seq_len"] // setup.chips, setup.ep)


# ------------------------------------------------------------------ a run
def run(cell, *, devices, peaks, seed: int, seconds: float, trace: bool,
        t0: float):
    """One run of a training cell.  Returns (result, checks)."""
    import jax
    t_imports = time.perf_counter() - t0
    setup = Setup(cell, seed, devices)
    prog = setup.check_steps()
    setup_s = time.perf_counter() - t0
    print(json.dumps({"phase": "setup", "setup_s": setup_s,
                      "before_setup": t_imports, **setup.phases}),
          flush=True)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        win = run_window(setup, seconds, trace_dir)
        events = (tracereduce.load_dir(trace_dir, len(devices))
                  if trace else None)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices)
    memory_peak = max(in_use, setup.program_bytes)
    setup.state.clear()
    mix = cell.traffic
    tokens_per_step = mix["global_batch"] * mix["seq_len"]
    n = len(win["steps"])
    tokens_per_s = n * tokens_per_step / win["seconds"]
    failed = sum(1 for st in win["steps"] if float(st["step_ok"]) < 0.5)
    loads = np.stack([st["expert_counts"] for st in win["steps"]])
    print(json.dumps({
        "phase": "window", "steps": n, "seconds": win["seconds"],
        "kernels": setup.kernels, "program_bytes": setup.program_bytes,
        "peak_bytes_in_use": in_use,
        "max_over_mean_expert_load": float(
            (loads.max(-1) / loads.mean(-1)).mean()),
        "dropped_frac": float(np.mean([st["dropped_frac"]
                                       for st in win["steps"]])),
        "setup_losses": prog["losses"]}), flush=True)

    t = time.perf_counter()
    ref = reference_readings(cell.config, setup.opt, capacity_of(setup),
                             setup.key, prog["batches"], groups=cell.chips,
                             devices=devices)
    print(json.dumps({"phase": "reference",
                      "seconds": time.perf_counter() - t}), flush=True)
    checks, correct = judge(gaps(prog, ref), cell.config["limits"]["train"])

    busy = window = None
    if trace:
        ctx = {"cell": cell, "config": cell.config, "traffic": mix,
               "peaks": peaks, "chips": cell.chips, "steps": win["steps"],
               "tokens_per_s": tokens_per_s, "events": events,
               "capacity": capacity_of(setup)}
        metrics = harness.read_per_layer(cell, ctx)
        busy, window = events.busy_s(), events.window_s()
        breakdown = events.breakdown()
    else:
        metrics = {"train_tokens_per_s": {"value": tokens_per_s,
                                          "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {m["name"]: metrics[m["name"]]
                   for m in cell.end_to_end() if m["name"] in metrics}
    result = {"correct": correct, "attempted": n, "failed": failed,
              "metrics": metrics,
              "device": harness.device_block(devices, memory_peak, busy,
                                             window)}
    if trace:
        result["breakdown"] = breakdown
    return result, checks
