"""The compile plane's scope tables (``telemetry/compiled.py``): the
rule that gives an ``op_name`` path its part, the parse of a compiled
program's text, and the two trainers' programs. The serving programs'
tables are held to the same rules in ``tests/test_decoder.py``."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import mesh as gmesh
from apex_tpu.models.gpt import GPTConfig
from apex_tpu.models.pretrain import (init_gpt_pretrain_params,
                                      make_gpt_pretrain_step)
from apex_tpu.optimizers import FusedAdam, clear_step_cache, make_train_step
from apex_tpu.telemetry import compiled


@pytest.mark.parametrize("op_name,part", [
    ("jit(decode_fn)/PatternDecoder/embed/gather", "embed"),
    # a Flax module's own name is on the path: the innermost part wins
    ("jit(decode_fn)/PatternDecoder/layer_3/attention/attention/cache/"
     "kv_gather/pallas_call", "cache"),
    ("jit(decode_fn)/PatternDecoder/layer_1/experts/mlp/experts/moe_router/"
     "top_k", "experts"),
    ("jit(decode_fn)/PatternDecoder/layer_1/experts/mlp/mlp/dot_general",
     "mlp"),
    ("jit(decode_fn)/PatternDecoder/layer_2/mixer/mamba_mixer/mixer/"
     "ssm_step/mul", "mixer"),
    ("jit(decode_fn)/head/cond/branch_1_fun/vmap()/sort", "head"),
    # the backward pass, and the layer scan
    ("jit(step)/transpose(jvp(GPTModel))/layers/while/body/layer/attention/"
     "attention/qkv/dot_general", "attention"),
    ("jit(step)/transpose(jvp(optimizer))/pad", "optimizer"),
    ("jit(step)/jvp(loss)/reduce_max", "loss"),
    # a jitted function's own name is no scope
    ("jit(step)/jit(loss)/add", None),
    ("jit(step)/jvp(GPTModel)/layers/while/body/dynamic_update_slice", None),
    # paths the compiler joined: the first with a part speaks
    ("jit(f)/mul;jit(f)/head/add", "head"),
    ("", None), (None, None),
])
def test_an_op_names_part_is_its_innermost(op_name, part):
    assert compiled.part_of(op_name) == part


TEXT = """HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/attention/mul"}
  ROOT %add.1 = f32[4]{0} add(%mul.1, %param_0), metadata={op_name="jit(f)/cache/add"}
}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%param_0.1), metadata={op_name="jit(f)/mlp/neg"}
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%arg), index=1
  %exp.3 = f32[4]{0} exponential(%gte.1), metadata={op_name="jit(f)/while/body/mixer/exp"}
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%gte.0, %exp.3)
}

%cond (arg.1: (s32[], f32[4])) -> pred[] {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%arg.1, %arg.1), direction=LT
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %copy.2 = f32[4]{0} copy(%x)
  %fusion.7 = f32[4]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/cache/add"}
  %fusion.8 = f32[4]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1
  %tuple.0 = (s32[], f32[4]{0}) tuple(%x, %fusion.8)
  %while.5 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %gte.9 = f32[4]{0} get-tuple-element(%while.5), index=1
}
"""


def test_a_text_is_parsed_into_a_scope_table():
    table = compiled.parse_hlo_text(TEXT)
    # the entry computation and the loop's body and condition; not the
    # fused computations' own instructions
    assert set(table["ops"]) == {
        "x", "copy.2", "fusion.7", "fusion.8", "tuple.0", "while.5", "gte.9",
        "arg", "gte.1", "exp.3", "gte.0", "tuple.1", "arg.1", "lt.1"}
    assert table["opcodes"]["fusion.7"] == "fusion"
    assert table["results"]["while.5"] == "(s32[], f32[4]{0})"
    assert table["fusion_parts"] == {"fusion.7": ["attention", "cache"],
                                     "fusion.8": ["mlp"]}
    parts = table["parts"]
    assert parts["fusion.7"] == "cache"      # two parts inside: its own
    assert parts["fusion.8"] == "mlp"        # unnamed: the one inside
    assert parts["copy.2"] == "cache"        # unnamed: its consumer's
    assert parts["exp.3"] == "mixer"
    assert "while.5" not in parts and "lt.1" not in parts


@pytest.fixture
def no_mesh():
    gmesh.destroy_mesh()
    yield
    gmesh.destroy_mesh()


def _compiles_while(fn):
    fired = []

    def listen(name, secs, **kw):
        if name == compiled.BACKEND_COMPILE_EVENT:
            fired.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        return fn(), len(fired)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _held_to_the_rules(table, parts):
    work = [n for n, opcode in table["opcodes"].items()
            if opcode not in compiled.PLUMBING]
    left = [(n, table["opcodes"][n], table["ops"][n]) for n in work
            if n not in table["parts"]]
    # what the source named and no part claims: the layer scan's own
    # (stacking the residuals, zeroing their cotangents, its counter)
    # (and, under a mesh, what the partitioner made and named itself)
    assert all("/layer_scan/" in u[2] for u in left
               if u[2].startswith("jit(")), left
    assert len(left) <= 0.2 * len(work), left
    assert set(table["parts"].values()) == parts
    assert table["missing_parts"] == []


@pytest.mark.parametrize("mesh", [None, {"batch": 2, "model": 2}],
                         ids=["one-device", "2x2"])
def test_a_mesh_train_step_is_split_by_part(rng, no_mesh, mesh):
    """One ``MeshTrainStep`` step, on one device and on a 2 x 2 mesh:
    one table, named ``jit_step``; forward and backward of the layers
    under ``attention`` / ``mlp`` / ``embed``, the logits under
    ``head``, the cross entropy under ``loss``, the unpack of the
    master, the gradient into the flat space and the update under
    ``optimizer``; pulling it compiles nothing."""
    gc.collect()
    compiled.forget_programs()
    if mesh:
        gmesh.initialize_mesh(**mesh, devices=jax.devices()[:4])
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, hidden_size=64,
                    num_layers=2, num_heads=4, dtype=jnp.float32)
    params = init_gpt_pretrain_params(cfg, jax.random.PRNGKey(0))
    step, state = make_gpt_pretrain_step(
        cfg, FusedAdam(lr=2e-3, impl="xla"))(params)
    toks = jnp.asarray(rng.randint(0, 128, (4, 33)), jnp.int32)
    state, loss = step(state, toks[:, :-1], toks[:, 1:])
    assert np.isfinite(float(loss))
    tables, fired = _compiles_while(compiled.scope_tables)
    assert fired == 0
    assert [t["name"] for t in tables] == ["jit_step"]
    assert tables[0]["signature"]["fn"] == "mesh_train_step"
    _held_to_the_rules(tables[0], {"embed", "attention", "mlp", "head",
                                   "loss", "optimizer"})
    # the backward pass lies under the same parts
    backward = [op for op in tables[0]["ops"].values() if "transpose(" in op]
    assert {compiled.part_of(op) for op in backward} >= {
        "attention", "mlp", "optimizer"}
    compiled.forget_programs()


def test_a_train_steps_program_is_the_optimizers(rng):
    """``optimizers.TrainStep``: the program is the update alone, all
    of it ``optimizer``."""
    gc.collect()
    compiled.forget_programs()
    clear_step_cache()
    opt = FusedAdam(lr=1e-3, impl="xla")
    state = opt.init({"w": jnp.asarray(rng.randn(300, 40), jnp.float32),
                      "b": jnp.asarray(rng.randn(40), jnp.float32)})
    g = jnp.asarray(rng.randn(*state.master.shape) * 0.1, jnp.float32)
    make_train_step(opt)(state, g)
    tables, fired = _compiles_while(compiled.scope_tables)
    assert fired == 0
    assert [t["name"] for t in tables] == ["jit_jitted"]
    assert set(tables[0]["parts"].values()) == {"optimizer"}
    assert tables[0]["missing_parts"] == []
    clear_step_cache()


def test_a_registration_never_raises_and_keeps_no_array():
    compiled.forget_programs()
    f = jax.jit(lambda x: x + 1)
    x = jnp.ones(3)
    compiled.register_program("jit_f", {"fn": "f"}, f, (x,))
    (entry,) = compiled._PROGRAMS.values()
    assert isinstance(entry["args"][0], jax.ShapeDtypeStruct)
    compiled.register_program("jit_g", {"fn": object()}, object(), (x,))
    assert len(compiled._PROGRAMS) == 1      # what cannot be lowered is not kept
    compiled.forget_programs()
