"""The named scopes of the compiled step: reading scope and phase from an
op_name, the map from a compiled program's HLO text, and every scope the
program names reaching the metadata of the tiny step compiled on the
CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import scopes


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/optimizer/mul", "optimizer.fwd"),
    ("jit(train_step)/jvp(lm_head)/while/body/dot_general", "lm_head.fwd"),
    ("jit(train_step)/transpose(jvp(lm_head))/dot_general", "lm_head.bwd"),
    ("jit(train_step)/jvp()/while/body/closed_call/dispatch/scatter",
     "dispatch.fwd"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/dot_general", "attention.remat"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "expert_ffn/pallas_call", "expert_ffn.bwd"),
    ("jit(train_step)/transpose(jvp(spag))/scatter-add", "spag.bwd"),
    ("jit(train_step)/transpose(jvp())/sprs/transpose(spag)/pad",
     "spag.bwd"),
    # the innermost scope wins
    ("jit(train_step)/jvp()/dispatch/gate/top_k", "gate.fwd"),
    # a jitted function named like a scope is not one
    ("jit(train_step)/jit(attention)/dot_general", None),
    ("jit(train_step)/jvp()/while/body/add", None),
])
def test_scope_of_op_name(op_name, want):
    assert scopes.scope_of(op_name) == want


HLO = '''HloModule jit_train_step

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(train_step)/optimizer/mul" stack_frame_id=3}
}

ENTRY %main.2 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %custom-call.3 = f32[4]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(attention))/pallas_call" stack_frame_id=5}
  ROOT %add.4 = f32[4]{0} add(%custom-call.3, %p), metadata={op_name="jit(train_step)/add"}
}
'''


def test_op_names_and_scope_map_from_hlo_text():
    names = scopes.op_names(HLO)
    # the fusion has no metadata of its own: its computation's root's
    assert names["fusion.1"] == "jit(train_step)/optimizer/mul"
    assert names["add.4"] == "jit(train_step)/add"
    assert "p" not in names
    # an instruction of a called computation without metadata: its caller's
    assert names["param_0"] == names["fusion.1"]
    assert scopes.scope_map(names) == {
        "fusion.1": "optimizer.fwd", "mul.1": "optimizer.fwd",
        "param_0": "optimizer.fwd", "custom-call.3": "attention.bwd"}


LOOP = '''ENTRY %main (p: bf16[192,8]) -> bf16[192,8] {
  %p = bf16[192,8]{1,0} parameter(0)
  ROOT %while.1 = bf16[192,8]{1,0} while(%p), condition=%cond, body=%body, metadata={op_name="jit(train_step)/transpose(jvp())/checkpoint/spag/add_any"}
}

%body (q: bf16[192,8]) -> bf16[192,8] {
  %q = bf16[192,8]{1,0} parameter(0)
  %fusion.2 = bf16[1,8]{1,0} fusion(%q), kind=kLoop, calls=%fused_computation.2
  ROOT %dynamic-update-slice.871 = bf16[192,8]{1,0} dynamic-update-slice(%q, %fusion.2)
}

%fused_computation.2 (r: bf16[192,8]) -> bf16[1,8] {
  %r = bf16[192,8]{1,0} parameter(0)
  ROOT %add.5 = bf16[1,8]{1,0} add(%r, %r), metadata={op_name="add"}
}
'''


def test_a_loop_the_compiler_wrote_takes_its_loops_scope():
    # an expanded scatter: a while loop whose body carries no metadata
    smap = scopes.scope_map(scopes.op_names(LOOP))
    assert smap["dynamic-update-slice.871"] == "spag.bwd"
    assert smap["fusion.2"] == "spag.bwd"


def test_device_ms_by_scope():
    from bench import tracereduce as tr
    ev = tr.Events({"/device:TPU:0": [("while.1", 0, 100e6),
                                      ("fusion.1", 10e6, 30e6),
                                      ("custom-call.3", 50e6, 40e6)]},
                   [(tr.WINDOW_SPAN, 0, 100e6)])
    ms = scopes.device_ms(scopes.op_ms(ev, 2), {
        "fusion.1": "optimizer.fwd", "custom-call.3": "attention.bwd"})
    assert ms == {"unscoped": pytest.approx(15.0),
                  "optimizer.fwd": pytest.approx(15.0),
                  "attention.bwd": pytest.approx(20.0)}
    assert scopes.scope_sum(ms, "optimizer", "attention") == \
        pytest.approx(35.0)


@pytest.mark.parametrize("microbatch,absent", [
    # the benchmark cell's step: one batch, the gather transposed by AD
    (0, {"sprs"}),
    # gradient accumulation: one explicit transpose of the stacked gather
    (2, set()),
])
def test_every_scope_reaches_the_compiled_step(microbatch, absent):
    import repro.configs as configs
    from repro.launch import inputs as inp
    from repro.launch import train as train_launch
    from repro.train import step as step_lib
    from repro.train.trainer import jit_train_step
    cfg = configs.get_smoke("gpt-moe-s").replace(remat=True)
    s = train_launch.build(cfg, train_launch.parse_args([
        "--arch", "gpt-moe-s", "--smoke", "--steps", "2",
        "--global-batch", "2", "--seq-len", "32",
        "--microbatch", str(microbatch)]))
    state = jax.eval_shape(lambda k: step_lib.init_state(cfg, k, 1),
                           jax.random.PRNGKey(0))
    shard = step_lib.state_shardings(cfg, s.mesh)
    state = jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sh), state, shard)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}
    pa = inp.concrete_plan(cfg, 1, "ring")
    text = jit_train_step(cfg, s.rt, s.tc).lower(state, batch,
                                                 pa).compile().as_text()
    names = scopes.op_names(text)
    named = {c for op in names.values() for c in
             map(scopes._base, op.split("/"))} & set(scopes.SCOPES)
    assert named == set(scopes.SCOPES) - absent
    smap = scopes.scope_map(names)
    if microbatch == 0:
        assert {"spag.fwd", "spag.bwd", "attention.remat",
                "expert_ffn.bwd", "lm_head.bwd"} <= set(smap.values())
