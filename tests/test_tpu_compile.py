"""Ask the TPU compiler, without a TPU.

The chip's compiler is installed beside jax and compiles for a chip
that is described and not attached, so what it refuses costs no chip
time: a block shape the Pallas lowering will not take, a construct the
Mosaic layout pass aborts on, a program that does not fit the device's
memory. Interpret-mode tests see none of these. The cases are the
kernels of the two main paths at the widths ``chip_smoke.py`` runs
them — the GPT-2 345M trainer and the paged-KV server — then the whole
train step at the smoke's batch. Nothing runs here, so nothing is said
about results or times.

``default_impl()`` sees the CPU in such a compile, so the kernels get
``impl="pallas"`` and the whole step the ``APEX_TPU_IMPL`` override.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from apex_tpu import _backend

#: what chip_smoke.py's trainer settles on for a v5e, and the memory
#: that chip reports (15.75 GiB of its 16 GB)
SMOKE_BATCH = 2
V5E_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def chip():
    """``shape, dtype -> ShapeDtypeStruct`` on one described v5e chip
    (``chip.devices`` lists all four); skipped where the topology
    cannot be described."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip
        pytest.skip(f"cannot describe a v5e topology: {e}")
    one = SingleDeviceSharding(topo.devices[0])

    def chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    chip.devices = list(topo.devices)
    return chip


@pytest.fixture(autouse=True)
def _no_compilation_cache():
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip (the next one warns)
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def assert_pool_stays_where_it_lies(text, shape):
    """A serving program's compiled text writes the donated pools in
    place: no ``copy`` instruction has the pool's shape, every mention
    of that shape carries the layout the parameter arrived in (an
    operand constraint of a kernel names the order alone, without
    tiles), and ``input_output_alias`` ties both pools to outputs."""
    import re

    pool = re.escape("bf16[%s]" % ",".join(str(n) for n in shape))
    arrived = re.findall(rf"= {pool}(\{{[^}}]*\}}) parameter\((\d+)\)",
                         text[text.index("ENTRY"):])
    assert len(arrived) == 2 and arrived[0][0] == arrived[1][0], arrived
    layout = arrived[0][0]                     # K and V, one layout
    assert not re.findall(rf"%[\w.-]+ = {pool}\S* copy\(", text)
    order = layout.split(":")[0].rstrip("}") + "}"
    for mention in set(re.findall(rf"{pool}(\{{[^}}]*\}})", text)):
        assert mention in (layout, order), (mention, layout)
    (alias,) = re.findall(r"input_output_alias=\{(.*?) \}, entry", text)
    for _, number in arrived:
        assert re.search(rf"\{{\d+\}}: \({number}, \{{\}}, may-alias\)",
                         alias), (number, alias)


def assert_experts_are_read_where_they_lie(text, *shapes):
    """A program's expert layers, in either form (``moe/held.py``
    ``expert_form``), multiply the held experts' weights as they
    arrived: no ``copy`` or ``transpose`` instruction has the shape of
    an expert weight array, and every mention of such a shape carries
    the tiling and order its parameters arrived in (an operand
    constraint of the ``moe_grouped`` kernel names the order alone). The
    memory space is no part of the comparison: the compiler may fetch
    a weight into ``S(1)`` by ``copy-start`` / ``copy-done``, which is
    the read itself."""
    import re

    entry = text[text.index("ENTRY"):]
    for shape in shapes:
        weight = re.escape("bf16[%s]" % ",".join(str(n) for n in shape))
        arrived = set(re.findall(
            rf"= {weight}(\{{[^}}]*\}}) parameter\(", entry))
        assert len(arrived) == 1, (shape, arrived)
        assert not re.findall(
            rf"%[\w.-]+ = {weight}\S* (?:copy|transpose)\(", text), shape
        mentions = {re.sub(r"S\(\d+\)", "", m) for m in re.findall(
            rf"{weight}(\{{[^}}]*\}})", text)}
        orders = {a.split(":")[0].rstrip("}") + "}" for a in arrived}
        assert arrived <= mentions <= arrived | orders, (shape, mentions,
                                                        arrived)


def _flash(sq, sk, *, causal, segs, b=16, h=16, hk=4, grad=False):
    from apex_tpu.ops.attention import flash_attention

    def build(chip):
        q = chip((b, h, sq, 64), jnp.bfloat16)
        k = chip((b, hk, sk, 64), jnp.bfloat16)
        seg = chip((b, sk), jnp.int32)

        def fwd(q, k, v, seg):
            return flash_attention(q, k, v, causal=causal,
                                   kv_segment_ids=seg if segs else None,
                                   impl="pallas")

        def fwd_bwd(q, k, v, seg):
            return jax.grad(lambda *a: jnp.sum(
                fwd(*a, seg).astype(jnp.float32)), argnums=(0, 1, 2))(
                    q, k, v)

        return (fwd_bwd if grad else fwd), (q, k, k, seg)

    return build


def _kv_gather(width, lanes=8, kv_heads=16, blocks=1025, layers=24):
    from apex_tpu.ops.kv_gather import kv_gather

    def build(chip):
        pool = chip((layers, blocks, 16, kv_heads, 128), jnp.bfloat16)

        def gather(k_pool, v_pool, layer, tables, lens):
            # two layers: the second writes into the first one's result
            first = kv_gather((k_pool, v_pool), layer, tables, lens,
                              impl="pallas")
            return kv_gather((k_pool, v_pool), layer + 1, tables, lens,
                             first, impl="pallas")

        return gather, (pool, pool, chip((), jnp.int32),
                        chip((lanes, width), jnp.int32),
                        chip((lanes,), jnp.int32))

    return build


def _latent_gather(width, lanes=32, blocks=65537, layers=6):
    """glm-4.7-flash's latent pool (one 640-wide row a token, one pool) at
    its decode program's lanes, then the absorbed form over what was
    gathered (``ops/mla_decode.py``): 20 heads, values of 512."""
    from apex_tpu.ops.kv_gather import kv_gather
    from apex_tpu.ops.mla_decode import mla_decode

    def build(chip):
        def decode(pool, layer, tables, lens, q):
            (rows,) = kv_gather((pool,), layer, tables, lens,
                                impl="pallas")
            return mla_decode(q, rows, lens, value_dim=512,
                              scale=256 ** -0.5, impl="pallas")

        return decode, (chip((layers, blocks, 16, 1, 640), jnp.bfloat16),
                        chip((), jnp.int32), chip((lanes, width), jnp.int32),
                        chip((lanes,), jnp.int32),
                        chip((lanes, 20, 640), jnp.bfloat16))

    return build


def _layer_norm(chip):
    from apex_tpu.ops.layer_norm import fused_layer_norm

    def fwd_bwd(x, w, b):
        return jax.grad(lambda *a: jnp.sum(fused_layer_norm(
            *a, impl="pallas").astype(jnp.float32)), argnums=(0, 1, 2))(
                x, w, b)

    w = chip((1024,), jnp.float32)
    return fwd_bwd, (chip((SMOKE_BATCH * 1024, 1024), jnp.bfloat16), w, w)


def _xentropy(chip):
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss

    def fwd_bwd(logits, labels):
        return jax.grad(lambda lg: jnp.sum(softmax_cross_entropy_loss(
            lg, labels, impl="pallas")))(logits)

    rows = SMOKE_BATCH * 1024
    return fwd_bwd, (chip((rows, 50304), jnp.float32),
                     chip((rows,), jnp.int32))


def _fused_adam(chip):
    from apex_tpu import multi_tensor as mt

    buf = chip((8 * 2**20,), jnp.float32)
    return (lambda p, m, v, g: mt.fused_adam_update(
        p, m, v, g, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, step=1,
        weight_decay=0.01, impl="pallas")), (buf, buf, buf, buf)


def _lamb_tree(dtype):
    # a layer's worth of GPT-2 345M leaves: small ones that share a
    # segment, and a kernel too large for one (the two-stage slice)
    shapes = [(1024,), (1024,), (3072, 1024), (3072,), (1024, 1024),
              (1024,), (4096, 1024), (4096,)]
    return {f"p{i}": jax.ShapeDtypeStruct(s, dtype)
            for i, s in enumerate(shapes)}


def _two_stage_lamb(chip):
    from apex_tpu import multi_tensor as mt

    space = mt.FlatSpace.create(_lamb_tree(jnp.float32))
    buf = chip((space.total,), jnp.float32)
    return (lambda p, m, v, g: mt.fused_lamb_update(
        p, m, v, g, space, lr=1e-3, step=1, weight_decay=0.01,
        impl="pallas")), (buf, buf, buf, buf)


def _segmented_lamb(dtype, sr_seed):
    from apex_tpu.multi_tensor.flat_buffer import segmented_space
    from apex_tpu.multi_tensor.segmented import (
        CHUNK,
        fused_lamb_segmented_update,
    )

    def build(chip):
        space, meta = segmented_space(_lamb_tree(dtype),
                                      seg_elems=16 * CHUNK)
        assert meta.small_segments and meta.large    # both paths in it
        f32 = chip((space.total,), jnp.float32)
        return (lambda p, m, v, g: fused_lamb_segmented_update(
            p, m, v, g, space, meta, lr=1e-3, step=1, weight_decay=0.01,
            use_nvlamb=True, impl="pallas", sr_seed=sr_seed)), (
                chip((space.total,), dtype), f32, f32, f32)

    return build


KERNELS = {
    "flash fwd+bwd (b,16,1024,64) causal": _flash(
        1024, 1024, causal=True, segs=False, b=SMOKE_BATCH, hk=16,
        grad=True),
    # one query over its gathered context, itself in its slot: sk = L
    "flash decode sk=1024": _flash(1, 1024, causal=False, segs=True),
    "flash decode sk=2048": _flash(1, 2048, causal=False, segs=True),
    # a key count no lane-aligned block divides is padded (_kv_pad)
    "flash decode sk=513": _flash(1, 513, causal=False, segs=True),
    "flash decode sk=1025": _flash(1, 1025, causal=False, segs=True),
    "flash decode sk=2049": _flash(1, 2049, causal=False, segs=True),
    # a 32-token chunk over 1024 cached tokens: sk = L + s
    "flash chunked prefill sq=32 sk=1056": _flash(
        32, 1056, causal=True, segs=True, b=2),
    # the benchmark's pool, 8 lanes at both of its width buckets
    "kv gather width 64": _kv_gather(64),
    "kv gather width 128": _kv_gather(128),
    # serve-longmix's pool and lanes, the full table and the window's
    "kv gather width 1024, 16 lanes": _kv_gather(
        1024, lanes=16, kv_heads=8, blocks=8192, layers=5),
    "kv gather width 320, 16 lanes": _kv_gather(
        320, lanes=16, kv_heads=8, blocks=8192, layers=5),
    # glm-4.7-flash's decode calls at the narrowest and widest tables
    "latent gather + mla_decode width 512, 32 lanes": _latent_gather(512),
    "latent gather + mla_decode width 4096, 32 lanes": _latent_gather(4096),
    "layer norm fwd+bwd": _layer_norm,
    "xentropy fwd+bwd vocab 50304": _xentropy,
    "fused adam, flat 8M": _fused_adam,
    "two-stage lamb": _two_stage_lamb,
    "segmented lamb fp32": _segmented_lamb(jnp.float32, None),
    "segmented lamb bf16 + stochastic rounding": _segmented_lamb(
        jnp.bfloat16, 7),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(chip, name):
    fn, args = KERNELS[name](chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") > 0


@pytest.mark.parametrize("chips", [1, 4])
def test_gpt2_345m_train_step_fits_and_holds_kernels(chip, monkeypatch,
                                                     chips):
    """The whole train step, as ``chip_smoke.py`` builds it, at the
    smoke's batch — on one described chip, and on the 2x2 mesh of all
    four (``--chips 4``): it fits the device with headroom and every
    fused op is a kernel in it. On the mesh each kernel must sit in a
    ``shard_map`` island (mesh/annotate.py ``on_shards``), or the
    compiler refuses the program: "Mosaic kernels cannot be
    automatically partitioned"."""
    import numpy as np
    from jax.sharding import Mesh

    from apex_tpu import mesh as gmesh
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.models.pretrain import init_gpt_pretrain_params
    from apex_tpu.optimizers import FusedAdam

    monkeypatch.setenv("APEX_TPU_IMPL", "pallas")
    _backend.default_impl.cache_clear()
    try:
        cfg = GPTConfig.gpt2_345m(attention_backend="flash",
                                  dtype=jnp.bfloat16)
        shapes = jax.eval_shape(
            lambda key: init_gpt_pretrain_params(cfg, key),
            jax.random.PRNGKey(0))
        opt = FusedAdam(lr=3e-4, weight_decay=0.01)
        if chips == 1:
            mesh = Mesh(np.asarray(chip.devices[:1]).reshape(1, 1, 1),
                        gmesh.MESH_AXES)
        else:
            mesh = gmesh.initialize_mesh(batch=2, model=2,
                                         devices=chip.devices)
        # what ``make_gpt_pretrain_step`` builds for a dense config;
        # its ``step.init`` places arrays, so the program is compiled
        # from shapes, which the plan's shardings put on the mesh
        step = gmesh.make_mesh_train_step(
            GPTModel(cfg), opt, gmesh.plan_gpt(shapes, mesh=mesh))
        state = jax.eval_shape(opt.init, shapes)
        tok = jax.ShapeDtypeStruct((SMOKE_BATCH, cfg.max_seq_len),
                                   jnp.int32)
        if chips == 1:
            state, tok = jax.tree.map(
                lambda x: chip(x.shape, x.dtype), (state, tok))
        compiled = step.lower(state, tok, tok).compile()
    finally:
        gmesh.destroy_mesh()
        _backend.default_impl.cache_clear()
    text = compiled.as_text()
    # flash fwd + 2 bwd, 3 layer norms fwd + bwd, the fused Adam sweep
    assert text.count("tpu_custom_call") == 10
    assert ("all-reduce" in text) == (chips == 4)
    assert (chip_smoke.program_bytes(compiled)
            <= chip_smoke.HEADROOM * V5E_BYTES)


def test_decode_program_gathers_once_a_layer(chip, monkeypatch):
    """The paged-KV server's decode program at the benchmark's head
    size, block size, batch and width (two layers of a narrower
    model): in the layer scan's body there is one ``kv_gather`` call,
    for K and V, the attention kernel's keys are the
    ``[b * kv_heads, width * block_size, head_dim]`` it wrote, and
    nothing holds every layer's context, slices a layer out of the
    pool or concatenates along the key axis. The gather writes into
    the arrays the scan carries, in place: the body holds no copy of
    the context, nor any pass over it but the gather (the token's own
    K/V goes in by updates in place), and the program zeroes it once,
    K and V, outside the scan and pinned to HBM. The host's arguments
    arrive as one int32 buffer."""
    import re

    from apex_tpu import serving
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    monkeypatch.setenv("APEX_TPU_IMPL", "pallas")
    _backend.default_impl.cache_clear()
    try:
        cfg = GPTConfig(vocab_size=512, max_seq_len=1024, hidden_size=512,
                        num_layers=2, num_heads=4, attention_backend="flash",
                        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        model = GPTModel(cfg)
        cache = serving.KVCache.for_config(cfg, num_blocks=512,
                                           block_size=16)
        shapes = jax.eval_shape(
            lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))
        params, state = jax.tree.map(
            lambda x: chip(x.shape, x.dtype),
            (shapes, jax.eval_shape(cache.init_state)))
        b, w = 8, 64
        text = serving.make_decode_step(model, cache).lower(
            "decode_step", params, state, b, w).compile().as_text()
    finally:
        _backend.default_impl.cache_clear()
    by_name = {}
    for name, operands in re.findall(
            r"%([\w-]+)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\", "
            r"operand_layout_constraints=\{(.*?)\}, frontend_attributes",
            text):
        by_name.setdefault(name, []).append(operands)
    assert len(by_name["kv_gather"]) == 1
    keys = f"bf16[{b * 4},{w * 16},128]"
    (attn,) = by_name["attention"]
    assert attn.count(keys) == 2 and "513" not in attn
    assert not re.search(r"bf16\[2,8,4,1024,128\]", text)     # all layers
    assert not re.search(r"bf16\[513,16,4,128\]", text)       # a pool slice
    assert not re.search(r"bf16\[8,4,10(25|88|\d\d),128\]\S* concatenate",
                         text)
    ctx = re.escape(f"bf16[{b},4,{w * 16},128]")
    (body,) = [c for c in text.split("\n\n") if "%kv_gather." in c]
    made = re.findall(rf"%([\w.-]+) = {ctx}\S* ([\w-]+)\(", body)
    assert {op for _, op in made} <= {"get-tuple-element",
                                      "dynamic-update-slice"}, made
    assert len(by_name["zero_context"]) == 1
    assert "%zero_context." not in body
    assert not re.search(rf"{ctx}\S* (copy|copy-start|broadcast)\(", text)
    # what the host hands the program is one int32 buffer
    from apex_tpu.serving.decode import packed_layout, packed_size

    size = packed_size(packed_layout("decode_step", b, w))
    assert re.findall(r"= s32\[([\d,]*)\]\S* parameter\(",
                      text[text.index("ENTRY"):]) == [str(size)]


@pytest.mark.parametrize("fn,seq", [("decode_step", 1),
                                    ("prefill_chunk", 128)])
def test_scanned_serving_programs_lower_on_the_mesh(chip, monkeypatch, fn,
                                                    seq):
    """The scanned GPT's cached programs on the described 2x2 mesh
    (``batch`` 2 x ``model`` 2), the checkpoint model-sharded and the
    pool split on its kv heads (docs/serving.md): every kernel that
    touches the gathered context sits in an ``on_shards`` island (the
    zeroing before the scan as well as the gather in it), or the
    compiler refuses the program ("Mosaic kernels cannot be
    automatically partitioned"). Each shard gathers its own lanes and
    heads: 4 of 8 lanes, 2 of 4 kv heads."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import mesh as gmesh, serving
    from apex_tpu.mesh import annotate
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    monkeypatch.setenv("APEX_TPU_IMPL", "pallas")
    _backend.default_impl.cache_clear()
    try:
        cfg = GPTConfig(vocab_size=512, max_seq_len=1024, hidden_size=512,
                        num_layers=2, num_heads=4, attention_backend="flash",
                        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        model = GPTModel(cfg)
        cache = serving.KVCache.for_config(cfg, num_blocks=512,
                                           block_size=16)
        mesh = gmesh.initialize_mesh(batch=2, model=2, devices=chip.devices)
        shapes = jax.eval_shape(
            lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            shapes, annotate.serving_param_shardings(shapes, mesh=mesh))
        pool = NamedSharding(mesh, P(None, None, None, gmesh.MODEL_AXIS, None))
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=pool),
            jax.eval_shape(cache.init_state))
        text = serving.make_decode_step(model, cache).lower(
            fn, params, state, 8, 64, seq).compile().as_text()
    finally:
        gmesh.destroy_mesh()
        _backend.default_impl.cache_clear()
    for kernel in ("zero_context", "kv_gather"):
        (call,) = re.findall(
            rf"%{kernel}\.\d+ = (\([^\n]*?\)) custom-call\(", text)
        assert call.count("bf16[4,2,1024,128]") == 2, call


@pytest.mark.parametrize("fn,batch,seq", [
    ("decode_step", 16, 1), ("prefill_chunk", 4, 1024)])
def test_trinity_cut_programs_fit_and_gather_the_window(chip, monkeypatch,
                                                        fn, batch, seq):
    """``Trinity-Large-Preview``'s five-layer cut at the benchmark's own
    sizes (``benchmark/configs/trinity-large-preview.json``: 4.32B
    parameters in bf16, a pool of 8192 blocks), its decode program at
    16 lanes and its chunk program at 4 x 1024, at the widest tables
    (1024 blocks, 16k positions): they fit a v5e beside nothing else,
    the four window layers gather and attend 5120 positions whatever
    the full table's width, the full layer 16384, and the expert
    layers' grouped products are ``moe_grouped`` kernels, in the decode
    program too (a quarter of a pair an expert: the grouped form)."""
    import json
    import re

    from apex_tpu import serving
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_pattern

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-large-preview.json")) as f:
        config = json.load(f)
    monkeypatch.setenv("APEX_TPU_IMPL", "pallas")
    _backend.default_impl.cache_clear()
    try:
        cfg = serve_pattern.decoder_config(config)
        model = PatternDecoder(cfg)
        cache = serving.KVCache.for_config(
            cfg, num_blocks=config["engine"]["num_blocks"],
            block_size=config["engine"]["block_size"])
        shapes = jax.eval_shape(
            lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))
        params, state = jax.tree.map(
            lambda x: chip(x.shape, x.dtype),
            (shapes, jax.eval_shape(cache.init_state)))
        width = 1024
        tail = cache.window_width(cfg.attention_window, width)
        compiled = serving.make_decode_step(model, cache).lower(
            fn, params, state, batch, width, seq=seq,
            window_table_width=tail).compile()
    finally:
        _backend.default_impl.cache_clear()
    assert tail * 16 == 5120
    assert chip_smoke.program_bytes(compiled) <= 0.8 * V5E_BYTES
    text = compiled.as_text()
    calls = re.findall(
        r"%([\w-]+)\.?\d* = (\([^=]*?\)|\S+) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", text)
    gathers = [res for name, res in calls if name == "kv_gather"]
    assert sorted(g.count(f"bf16[{batch},8,5120,128]") for g in gathers) \
        == [0, 2, 2, 2, 2]
    assert sum(g.count(f"bf16[{batch},8,16384,128]") for g in gathers) == 2
    names = [name for name, _ in calls]
    assert names.count("attention_window") == 4
    assert names.count("attention") == 1
    assert names.count("moe_grouped") == 12           # 3 products x 4 layers
    assert "ragged-dot-none" not in names
    assert_pool_stays_where_it_lies(text, state.pools[0].shape)


def _mellum2_program(chip, monkeypatch, fn, batch, seq):
    """``Mellum2-12B-A2.5B``'s eight-layer cut at the benchmark's own
    sizes, program ``fn`` at ``batch`` x ``seq`` over the widest tables
    (1024 blocks), lowered for one described v5e: ``(config, parameter
    shapes, cache state shapes, the lowered program, the window
    layers' table width)``."""
    import json

    from apex_tpu import serving
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_mellum

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        config = json.load(f)
    monkeypatch.setenv("APEX_TPU_IMPL", "pallas")
    _backend.default_impl.cache_clear()
    try:
        cfg = serve_mellum.decoder_config(config)
        model = PatternDecoder(cfg)
        cache = serving.KVCache.for_config(
            cfg, num_blocks=config["engine"]["num_blocks"],
            block_size=config["engine"]["block_size"])
        shapes = jax.eval_shape(
            lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))
        params, state = jax.tree.map(
            lambda x: chip(x.shape, x.dtype),
            (shapes, jax.eval_shape(cache.init_state)))
        width = 1024
        tail = cache.window_width(cfg.attention_window, width)
        lowered = serving.make_decode_step(model, cache).lower(
            fn, params, state, batch, width, seq=seq,
            window_table_width=tail)
    finally:
        _backend.default_impl.cache_clear()
    return cfg, shapes, state, lowered, tail


def test_mellum2_chunk_program_lowers_two_kernel_bodies(chip, monkeypatch):
    """The set-up guard (``ops/moe_grouped.py``: one jit, one kernel
    body a shape). A warm start lowers every program again to find it
    in the compile cache, and a kernel entered bare is lowered to
    Mosaic at every call site: 24 bodies in Mellum2's chunk program,
    10-19 s of ``setup_s`` a cell (PR 38). Through the jitted entry
    point its 24 products lower two bodies, gate and up sharing one."""
    import re

    *_, lowered, _ = _mellum2_program(chip, monkeypatch, "prefill_chunk",
                                      4, 1024)
    text = lowered.as_text()
    assert re.findall(r'kernel_name = "(\w+)"', text).count(
        "moe_grouped") == 2
    assert "ragged_dot" not in text


@pytest.mark.parametrize("fn,batch,seq", [
    ("decode_step", 16, 1), ("prefill_chunk", 4, 1024)])
def test_mellum2_cut_programs_fit_and_gather_the_tail(chip, monkeypatch,
                                                      fn, batch, seq):
    """``Mellum2-12B-A2.5B``'s eight-layer cut at the benchmark's own
    sizes (``benchmark/configs/mellum2-12b-a2.5b.json``: hidden 2304,
    8 query heads a KV head, 64 experts all held, a 98304-row head, a
    pool of 9216 blocks), its decode program at 16 lanes and its chunk
    program at 4 x 1024, at the widest tables (1024 blocks): they fit a
    v5e beside nothing else, the six window layers gather and attend a
    tail of 80 blocks (1280 positions) and the two full layers 16384,
    every layer's three expert products are ``moe_grouped`` kernels in
    the chunk program and, in the decode program, batched dense
    products; in both the weights stay where they lie."""
    import re

    cfg, shapes, state, lowered, tail = _mellum2_program(
        chip, monkeypatch, fn, batch, seq)
    compiled = lowered.compile()
    assert tail == 80 and cfg.hidden_size == 2304
    assert cfg.num_heads // cfg.num_kv_heads == 8
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert 3.78e9 < n < 3.81e9          # 8 x 417.75M + 453.0M: 7.59 GB
    assert chip_smoke.program_bytes(compiled) <= 0.8 * V5E_BYTES
    text = compiled.as_text()
    calls = re.findall(
        r"%([\w-]+)\.?\d* = (\([^=]*?\)|\S+) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", text)
    gathers = [res for name, res in calls if name == "kv_gather"]
    assert sorted(g.count(f"bf16[{batch},4,1280,128]") for g in gathers) \
        == [0, 0, 2, 2, 2, 2, 2, 2]
    assert sum(g.count(f"bf16[{batch},4,16384,128]") for g in gathers) == 4
    names = [name for name, _ in calls]
    assert names.count("attention_window") == 6
    assert names.count("attention") == 2
    assert "ragged-dot-none" not in names
    # 16 rows x top-8 over 64 in a decode call: two pairs an expert, the
    # dense form; a chunk's 8192 pairs: the grouped form
    assert names.count("moe_grouped") == (0 if fn == "decode_step" else 24)
    assert_experts_are_read_where_they_lie(
        text, (64, 2304, 896), (64, 896, 2304))
    # four KV heads fill half a bf16 tile: one scatter over all lanes
    # had the pools converted whole, there and back, in every call
    assert_pool_stays_where_it_lies(text, state.pools[0].shape)


@pytest.mark.parametrize("fn,batch,seq", [
    ("decode_step", 64, 1), ("prefill_chunk", 2, 1024),
    ("prefill_chunk", 1, 1024), ("prefill_step", 1, 512)])
def test_granite_cut_programs_fit_and_leave_the_state_where_it_lies(
        chip, monkeypatch, fn, batch, seq):
    """``granite-4.0-h-small``'s ten-layer cut at the benchmark's own
    sizes (``benchmark/configs/granite-4.0-h-small.json``: hidden 4096,
    nine Mamba-2 layers of 128 heads x 64 with a state of 128 and one
    attention layer of 32 / 8 heads, 9 of 72 experts held beside a
    shared MLP, a tied head of 100352 rows, 64 state slots, a pool of
    16384 blocks with ONE layer), its decode program at 64 lanes, a
    chunk program at two lanes and at one, and a whole-prompt program
    at one, tables of 512 blocks: they fit a v5e, the one attention
    layer gathers and attends 8192 positions, every layer's three
    expert products are ``moe_grouped`` kernels in the prefill programs
    and batched dense products in the decode program, over weights that
    stay where they lie, the decode
    program steps its nine state layers by the ``ssm_step`` kernel, and
    no ``copy`` has the shape of a K/V pool or of a state pool (a
    one-lane program used to lay the 2.45 GB pool out anew around its
    one slice; the slot count with its trash slot, 65, is the leading
    dimension of the state pools and of parts of them alone:
    ``ssm_state_copy_pct`` finds the state by it)."""
    import json
    import re

    from apex_tpu import serving
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_granite

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    engine = config["engine"]
    monkeypatch.setenv("APEX_TPU_IMPL", "pallas")
    _backend.default_impl.cache_clear()
    try:
        cfg = serve_granite.decoder_config(config)
        model = PatternDecoder(cfg)
        cache = serving.KVCache.for_config(
            cfg, num_blocks=engine["num_blocks"],
            block_size=engine["block_size"],
            state_slots=engine["state_slots"])
        shapes = jax.eval_shape(
            lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))
        params, state = jax.tree.map(
            lambda x: chip(x.shape, x.dtype),
            (shapes, jax.eval_shape(cache.init_state)))
        compiled = serving.make_decode_step(model, cache).lower(
            fn, params, state, batch, engine["min_width_bucket"],
            seq=seq).compile()
    finally:
        _backend.default_impl.cache_clear()
    n = sum(x.size for x in jax.tree.leaves(shapes))
    # 9 x 121.5M + 61.1M outside the experts, 10 x 85.0M held, 411M: 4.83 GB
    assert 2.40e9 < n < 2.43e9
    assert state.pools[0].shape == (1, 16385, 16, 8, 128)
    assert [s.shape for s in state.state] == [(65, 9, 128, 64, 128),
                                              (65, 9, 3 * 8448)]
    assert chip_smoke.program_bytes(compiled) <= 0.85 * V5E_BYTES
    text = compiled.as_text()
    calls = re.findall(
        r"%([\w-]+)\.?\d* = (\([^=]*?\)|\S+) custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", text)
    names = [name for name, _ in calls]
    assert "ragged-dot-none" not in names
    # 64 rows x top-10 over 72 in a decode call: 8.9 pairs an expert,
    # the dense form; the prefill programs' thousands: the grouped form
    assert names.count("moe_grouped") == (0 if fn == "decode_step" else 30)
    assert_experts_are_read_where_they_lie(
        text, (9, 4096, 768), (9, 768, 4096))
    assert names.count("attention") == 1
    assert names.count("ssm_step") == (9 if fn == "decode_step" else 0)
    if fn != "prefill_step":                          # over the cache
        gathers = [res for name, res in calls if name == "kv_gather"]
        assert [g.count(f"bf16[{batch},8,8192,128]") for g in gathers] == [2]
    assert_pool_stays_where_it_lies(text, state.pools[0].shape)
    # the state pools: donated, aliased to outputs, never copied whole
    for pool in state.state:
        shape = ",".join(str(d) for d in pool.shape)
        assert not re.findall(
            rf"%[\w.-]+ = \w+\[{shape}\]\S* copy\(", text), shape
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(r"= \w+\[65,[\d,]*\]\S* parameter\(", entry)) == 2
    # 65 is the leading dimension of the state pools (and of parts of
    # them) and no other array's
    for dims in set(re.findall(r"\w+\[([\d,]+)\]", text)):
        if "65" in dims.split(","):
            assert dims.split(",").index("65") == 0 and dims.split(
                ",")[-1] in ("128", "25344"), dims
