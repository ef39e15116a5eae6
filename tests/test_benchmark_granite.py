"""The benchmark's additions for ``granite-4.0-h-small``, rehearsed on
the CPU at toy size: the ``serve_granite`` driver through ``run.py`` (a
toy configuration, deck and manifest under
``benchmark/tests/rehearsal/``), the configuration file against the
published widths, the cell's arithmetic and its kernel's price, the
deck, the state metrics' specifications, the controls, and the
benchmark's copy of the reference. The device check is stubbed here, in
the test: the benchmark itself refuses a CPU.
"""

import json
import os
import re

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal")
CELL = "granite-4.0-h-small.serve-sessions"
TOY = "toy-granite.toy-sessions"


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def on_the_cpu(monkeypatch, manifest_path=None):
    from benchmark import run

    monkeypatch.setattr(run, "accelerator", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(run, "peaks_for", lambda kind, dirs: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: None)
    return dict(manifest_path=manifest_path or os.path.join(
        REHEARSAL, "BENCHMARK.granite.json"),
                data_dirs=[REHEARSAL, run.BENCH_DIR])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_granite_driver(monkeypatch, capsys, trace,
                                         rehearsal_manifest):
    from benchmark import run

    where = on_the_cpu(monkeypatch, rehearsal_manifest(
        "BENCHMARK.granite.json", TOY, CELL))
    rc = run.main(["--workload", TOY, "--seed", str(2**31 + 11),
                   "--seconds", "0.5", "--trace", str(trace)], **where)
    notes = capsys.readouterr().out.strip().splitlines()
    line = json.loads(notes[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0, notes
    assert line["attempted"] > 0 and line["window_compilations"] == 0
    if trace:
        assert {"decode_step_ms", "batch_fill_pct", "gather_live_pct",
                "state_share_of_cache_pct"} <= set(line["metrics"])
        # 3 layers x (8 x 16 x 16 + 3 x 160) x 4 B = 30 KB a session
        # against 1 KiB a block of 8 tokens in the one layer with keys
        assert 50 < line["metrics"]["state_share_of_cache_pct"]["value"] < 100
        # no device plane on the CPU: the trace readers return nothing
        assert not {"ssm_state_pct", "ssm_state_copy_pct",
                    "ssm_step_roofline", "moe_expert_pct",
                    "attention_roofline.sessions",
                    "prefill_call_ms.sessions",
                    "pool_relayout_pct"} & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "itl_p95_ms",
                                        "setup_s"}
    # drained in every run; a turn in a reused slot and a document over
    # check_prompt_over were checked, the tokens and the logits that the
    # window's own dispatches computed for them
    assert any("drained: 0 block(s) and 0 state slot(s) held" in n
               for n in notes), notes
    checked = [n for n in notes if n.startswith("# request")]
    assert len(checked) == 2 and all(
        "kept from the dispatches that served them" in n
        for n in checked), notes
    assert any("followed for their logits: (" in n for n in notes), notes
    assert any("programs warmed" in n and "('decode_step', 4, 16)" in n
               for n in notes), notes


def test_a_state_in_the_wrong_lane_is_refused(monkeypatch, capsys,
                                              rehearsal_manifest):
    """What ``correct`` compares comes from the window's own
    dispatches, its decode calls with every lane live among them: a
    decode program that hands each live lane the state slot of the
    live lane beside it (nothing where one sequence is alone among
    dummies, as a replay after the window would have it) is not
    correct, by the logits."""
    from apex_tpu.serving.decode import DecodeStep
    from benchmark import run

    decode = DecodeStep.decode

    def beside(self, params, state, tokens, positions, tables, sampling=None,
               window=None, slots=None):
        real = np.flatnonzero(slots != self.cache.state_slots)
        slots = slots.copy()
        slots[real] = slots[np.roll(real, 1)]
        return decode(self, params, state, tokens, positions, tables,
                      sampling=sampling, window=window, slots=slots)

    monkeypatch.setattr(DecodeStep, "decode", beside)
    where = on_the_cpu(monkeypatch, rehearsal_manifest(
        "BENCHMARK.granite.json", TOY, CELL))
    run.main(["--workload", TOY, "--seed", "77", "--seconds", "0.5",
              "--trace", "0"], **where)
    notes = capsys.readouterr().out.strip().splitlines()
    line = json.loads(notes[-1])
    assert line["correct"] is False and line["failed"] == 0, notes
    checked = [n for n in notes if n.startswith("# request")]
    off = [float(re.search(r"leave the reference's by (\S+) of", n).group(1))
           for n in checked]
    # every token still the reference's argmax: the logits refuse it
    assert len(off) == 2 and max(off) > 0.01, notes
    assert all(re.search(r"(\d+) tokens served, \1 of them", n)
               for n in checked), notes


def test_step_sequence_does_not_depend_on_the_seed():
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_granite

    config = load("benchmark", "tests", "rehearsal", "configs",
                  "toy-granite.json")
    deck = load("benchmark", "tests", "rehearsal", "traffic",
                "toy-sessions.json")["clients"]
    cfg = serve_granite.decoder_config(config)
    runs = [serve_granite.reachable_programs(
        PatternDecoder(cfg), cfg, config["engine"], deck, seed,
        config["vocab_size"], 300) for seed in (1, 2**31 + 5)]
    assert runs[0][1] == runs[1][1] and len(runs[0][1]) > 300
    # nor who ends when, so whom the run follows for its logits
    assert ([(at, req.id) for at, req in runs[0][2]]
            == [(at, req.id) for at, req in runs[1][2]]) and runs[0][2]
    # no window layer: no second table in any key
    assert {len(k) for k in runs[0][0] if k[0] == "decode_step"} == {3}
    assert {len(k) for k in runs[0][0] if k[0] != "decode_step"} == {4}


def test_the_cells_deck_reaches_nine_programs():
    """The committed deck dealt dry to the engine at the cell's own
    settings: one decode program (64 lanes, tables of 512 blocks),
    whole-prompt and chunk programs of 512 and 1024 rows at one or two
    lanes, nine in all, the same for any seed; the 64 first requests
    have all been served well before the lead-in ends; a prefill-type
    call in about two steps of five."""
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_granite

    config = load("benchmark", "configs", "granite-4.0-h-small.json")
    traffic = load("benchmark", "traffic", "serve-sessions.json")
    cfg = serve_granite.decoder_config(config)
    keys, sequence, ended = serve_granite.reachable_programs(
        PatternDecoder(cfg), cfg, config["engine"], traffic["clients"], 7,
        config["vocab_size"],
        traffic["lead_in_steps"] + traffic["horizon_steps"])
    assert sorted(k for k in keys if k[0] == "decode_step") == [
        ("decode_step", 64, 512)]
    assert {k[1:] for k in keys if k[0] != "decode_step"} == {
        (b, s, 512) for b in (1, 2) for s in (512, 1024)}
    assert len(keys) == 9, keys
    assert ended is not None
    prefills = sum(k[0] != "decode_step" for k in sequence)
    decodes = sum(k[0] == "decode_step" for k in sequence)
    assert 0.25 < prefills / decodes < 0.6


def test_configuration_keeps_every_published_width():
    config = load("benchmark", "configs", "granite-4.0-h-small.json")
    manifest = load("BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "granite-4.0-h-small")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_local_experts"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
        "config.json")
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768,
        "logits_scaling": 16, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352}
    assert {k: config[k] for k in published} == published
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert config["num_hidden_layers"] == 10
    assert config["layer_types"] == period
    assert config["num_local_experts"] == 9
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["layer_types"] == period * 4
    assert config["published"]["num_local_experts"] == 72
    deployment = config["deployment"]
    assert deployment["chips_per_layer"] == 8
    assert deployment["held_experts"] == [0, 9] \
        and deployment["router_width"] == 72
    # every other key is the file's own: nothing of the source is
    # missing or renamed
    own = {"name", "source", "reduced", "published", "deployment",
           "assumed", "engine", "reference_tolerance_ulps",
           "reference_choice_band", "reference_excused_margin",
           "reference_excused_share_max", "reference_logit_error_max",
           "reference_controls", "reference_tolerance_why"}
    assert set(config) - set(published) - own == {
        "num_hidden_layers", "layer_types", "num_local_experts"}
    for reason in ("intermediate_size", "in_proj_order", "gate_before_norm",
                   "time_step_limit", "state_dtype", "init_why"):
        assert len(config["assumed"][reason]) > 40, reason
    from apex_tpu import serving
    from benchmark.drivers import serve_granite

    cfg = serve_granite.decoder_config(config)
    assert [a for a, _ in cfg.layers] == ["mamba"] * 5 + ["full"] \
        + ["mamba"] * 4
    assert cfg.moe_cfg().held_range == (0, 9) and cfg.num_experts == 72
    assert (cfg.attention_scale, cfg.head_dim) == (1 / 128, 128)
    assert cfg.rotary_of("full") is None and not cfg.qk_norm
    # the paged pool has ONE layer, not ten; a slot holds nine layers
    cache = serving.KVCache.for_config(
        cfg, num_blocks=config["engine"]["num_blocks"], block_size=16,
        state_slots=config["engine"]["state_slots"])
    assert cache.num_layers == 1 and cfg.num_kv_layers == 1
    assert cfg.kv_layers == (5,) and len(cfg.state_layers) == 9
    assert cache.slot_bytes() == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)


def test_the_cells_arithmetic():
    """``costs_granite`` against the numbers the issue, the cell's
    ``why`` and ``PERF.md`` quote."""
    from benchmark import costs_granite

    config = load("benchmark", "configs", "granite-4.0-h-small.json")
    assert costs_granite.session_state_bytes(config) == pytest.approx(
        38.2e6, rel=0.01)
    assert costs_granite.kv_bytes_per_token(config) == 4096
    p = costs_granite.parameters(config)
    assert p["mamba"] == pytest.approx(102.3e6, rel=0.005)
    assert p["attention"] == pytest.approx(41.9e6, rel=0.005)
    assert p["expert"] == 3 * 4096 * 768
    assert p["total"] == pytest.approx(2.4147e9, rel=0.001)
    # the published model: 40 layers, 72 experts
    whole = dict(config, layer_types=config["published"]["layer_types"],
                 num_local_experts=72)
    assert costs_granite.parameters(whole)["total"] == pytest.approx(
        32.2e9, rel=0.01)
    step = costs_granite.decode_step_bytes(config, 64)
    assert step["state"] == pytest.approx(4.9e9, rel=0.01)
    assert step["mamba_weights"] == pytest.approx(1.84e9, rel=0.01)
    assert step["expert_weights"] == pytest.approx(2.09e9, rel=0.01)
    assert step["head"] == pytest.approx(0.82e9, rel=0.01)
    assert step["state"] > max(v for k, v in step.items() if k != "state")


def test_the_manifest_only_gained_entries():
    manifest = load("BENCHMARK.json")
    # the fifth configuration and the seventh cell: later PRs append
    assert manifest["workloads"][6] == {
        "name": CELL, "config": "granite-4.0-h-small",
        "traffic": "serve-sessions", "chips": 1,
        "why": manifest["workloads"][6]["why"]}
    assert manifest["configs"][4]["name"] == "granite-4.0-h-small"
    assert len(manifest["configs"]) >= 5 and len(manifest["workloads"]) >= 7
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    reported = {m["name"] for m in manifest["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    per_layer = [m["name"] for m in manifest["per_layer"]
                 if CELL in m.get("workloads", ())]
    # PR 37's split of a call by part of the model came as data, after
    # this cell's own six
    split = [name for name in per_layer if "_call_ms." in name
             and not name.startswith("prefill_call_ms.")]
    assert split == [
        "decode_call_ms.attention", "decode_call_ms.cache",
        "decode_call_ms.experts", "decode_call_ms.mixer",
        "decode_call_ms.mlp", "decode_call_ms.head",
        "decode_call_ms.unscoped", "chunk_call_ms.experts",
        "chunk_call_ms.head"] == per_layer[-9:]
    # this cell's own six came before the thirteen scope metrics, and
    # what came as data after it before them too
    own = next(i for i, m in enumerate(manifest["per_layer"])
               if m["name"] == "ssm_step_roofline") + 1
    assert own == 36
    per_layer = per_layer[:-9]
    assert per_layer[-6:] == [
        "ssm_state_pct", "ssm_state_copy_pct", "state_share_of_cache_pct",
        "prefill_call_ms.sessions", "attention_roofline.sessions",
        "ssm_step_roofline"]
    assert set(per_layer[:-6]) == {
        "decode_step_ms", "batch_fill_pct", "host_gap_ms",
        "host_gap_schedule_ms", "host_gap_build_ms", "host_gap_dispatch_ms",
        "host_gap_sync_ms", "engine_dispatches_per_step", "moe_expert_pct",
        "device_idle_pct.serve", "gather_live_pct", "pool_relayout_pct"}
    for name in per_layer:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json")), name
    # a list that gained the cell gained it at its end: after it come
    # only cells that later PRs appended
    later = {w["name"] for w in manifest["workloads"][7:]}
    for m in manifest["per_layer"][own - 6:own]:
        assert m["workloads"][0] == CELL and set(m["workloads"][1:]) <= later
    for m in manifest["end_to_end"] + manifest["per_layer"][:own - 6]:
        if CELL in m.get("workloads", ()):
            after = m["workloads"][m["workloads"].index(CELL) + 1:]
            assert set(after) <= later, m["name"]


def _toy_run(tmp_path, events, facts):
    import types

    from benchmark import trace_reduce

    at = 1000
    placed = []
    for text, ns, stats in events:
        placed.append([text, at, ns, stats])
        at += ns + 100
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.traced", 0, at, {}]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": placed}]}]}))
    return types.SimpleNamespace(
        reduced=trace_reduce.reduce(trace_reduce.from_json(str(path))),
        facts=facts)


STATE = "f32[65,9,128,64,128]"
#: the kernel's call as a chip's trace names it (my chip run, PR 35)
STEP_CALL = (
    f"%ssm_step.3 = (f32[64,4,64,32]{{3,2,1,0:T(8,128)}}, {STATE}"
    "{4,3,2,1,0:T(8,128)}) custom-call(s32[64]{0:T(128)} %slots, "
    "s32[64]{0:T(128)} %fresh, f32[64,4,64,32]{3,2,1,0:T(8,128)} %a, "
    "f32[64,4,64,32]{3,2,1,0:T(8,128)} %dx, f32[64,1,128]{2,1,0:T(1,128)} "
    f"%b, f32[64,1,128]{{2,1,0:T(1,128)}} %c, {STATE}{{4,3,2,1,0:T(8,128)}} "
    '%pool), custom_call_target="tpu_custom_call", operand_layout_'
    "constraints={}")


def test_ssm_state_pct_and_the_kernels_roofline_find_it_by_name(tmp_path):
    """The two kernel metrics' specifications through their readers on
    a toy trace: the calls named ``ssm_step`` count, the compiler's own
    fusions of the same shapes do not; a call is priced by its LANES'
    states (64 x 4.19 MB read and written), not by the pool it is
    handed (2.45 GB), and by 6 H P N operations a lane."""
    from benchmark import costs_granite, trace_reduce
    from benchmark.readers import op_share, roofline

    run = _toy_run(tmp_path, [
        (STEP_CALL, 800_000, {}),
        (f"%fusion.9 = {STATE}{{4,3,2,1,0}} fusion(%state, %u)", 700_000, {}),
        ("%fusion.2 = bf16[64,16768]{1,0} fusion(%u, %w)", 500_000, {})], {})
    spec = load("benchmark", "layer_metrics", "ssm_state_pct.json")
    assert spec["reader"] == "op_share"
    assert op_share.read(spec["params"], run) == pytest.approx(40.0)
    _, _, result = trace_reduce.parse_hlo(STEP_CALL)
    operands = STEP_CALL.partition(result)[2].partition(
        "custom_call_target")[0]
    flops, nbytes = costs_granite.ssm_step_call(
        trace_reduce.shapes(result), trace_reduce.shapes(operands))
    state = 128 * 64 * 128
    assert flops == 6.0 * 64 * state
    small = 3 * 64 * 4 * 64 * 32 * 4 + 2 * 64 * 128 * 4 + 2 * 64 * 4
    assert nbytes == 2 * 64 * state * 4 + small
    spec = load("benchmark", "layer_metrics", "ssm_step_roofline.json")
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run.notes = []
    share = roofline.read(spec["params"], run)
    assert share == pytest.approx(100 * (nbytes / 819e9) / 800e-6)
    assert 75 < share < 90 and "memory" in run.notes[0]


ROWS = "bf16[65,9,25344]"
COPY_OPS = {
    # the state by XLA's gather and scatter, as the compiler named it
    # in call 1's program: a gather inside a fusion, the update fused
    # into the scatter
    "gather": (f"%fusion.60 = f32[64,128,64,128]{{3,2,1,0}} fusion("
               f"{STATE}{{4,3,2,1,0}} %state, s32[64]{{0}} %slots), "
               "kind=kLoop, calls=%fused_computation.12", 3000),
    "scatter": (f"%fusion.79 = {STATE}{{4,3,2,1,0}} fusion({STATE}"
                "{4,3,2,1,0} %state, s32[64]{0} %slots, f32[64,128,64,128]"
                "{3,2,1,0} %a), kind=kLoop, calls=%fused_computation.31",
                2000),
    "copy": (f"%copy.500 = {STATE}{{4,3,2,0,1}} copy({STATE}{{4,3,2,1,0}} "
             "%state)", 4000),
    "step": (STEP_CALL, 3000),
    "rows": (f"%fusion.7 = bf16[64,25344]{{1,0}} fusion({ROWS}{{2,1,0}} "
             "%rows, s32[64]{0} %slots), kind=kLoop, "
             "calls=%fused_computation.3", 500),
    "slice": (f"%dynamic-slice.2 = f32[1,1,128,64,128]{{4,3,2,1,0}} "
              f"dynamic-slice({STATE}{{4,3,2,1,0}} %state, s32[] %slot, "
              "s32[] %c, s32[] %c, s32[] %c, s32[] %c)", 400),
    "second_read": ("%fusion.63 = f32[64,128,64]{2,1,0} fusion("
                    "f32[64,128,64,128]{3,2,1,0} %new, f32[64,128]{1,0} %c)",
                    1000),
    "other_gather": ("%gather.9 = bf16[64,4096]{1,0} gather(%table, %ids)",
                     1000),
}


@pytest.mark.parametrize("ops,want", [
    (("gather", "scatter", "second_read", "other_gather"),
     100 * 5000 / 7000),
    (("step", "other_gather"), 0.0),
    (("step", "rows", "slice", "other_gather"), 100 * 900 / 4900),
    (("copy", "step"), 100 * 4000 / 7000)],
    ids=["by-slot", "where-it-lies", "the-rows-by-slot",
         "a-whole-pool-copy"])
def test_ssm_state_copy_pct_counts_state_moved_by_slot(tmp_path, ops, want):
    """Every operation but the ``ssm_step`` kernel whose result or
    operands hold the slot count (65 with the trash slot) counts,
    under whatever name the compiler gave it: call 1's gather inside a
    fusion and its update fused into a scatter, a whole-pool copy, the
    convolution's rows read by slot, a prefill program's slice a lane.
    The kernel on the pool where it lies does not, nor a gather of
    another array, nor work on the copy once it is out of the pool."""
    from benchmark.readers import op_share

    spec = load("benchmark", "layer_metrics", "ssm_state_copy_pct.json")
    assert spec["reader"] == "op_share"
    run = _toy_run(tmp_path, [(*COPY_OPS[name], {}) for name in ops],
                   {"state_slots": 65})
    assert op_share.read(spec["params"], run) == pytest.approx(want)


def test_the_deck_is_what_its_generator_draws():
    import subprocess
    import sys

    path = os.path.join(ROOT, "benchmark", "traffic", "serve-sessions.json")
    doc = load("benchmark", "traffic", "serve-sessions.json")
    assert doc["drawn_from"] == {
        "generator_seed": 35, "clients": 64, "requests_per_client": 12,
        "prompt": {"dist": "mixture", "parts": [
            {"name": "document", "p": 0.25, "dist": "lognormal",
             "median": 4096, "sigma": 0.3, "clip": [2048, 7168]},
            {"name": "turn", "p": 0.75, "dist": "lognormal",
             "median": 512, "sigma": 0.6, "clip": [64, 2048]}]},
        "output": {"dist": "lognormal", "median": 256, "sigma": 0.5,
                   "clip": [64, 768]}}
    assert doc["driver"] == "serve_granite"
    assert (doc["lead_in_steps"], doc["trace_steps"]) == (400, 40)
    prompts = np.array([p for c in doc["clients"] for p, _ in c])
    outputs = np.array([o for c in doc["clients"] for _, o in c])
    assert len(doc["clients"]) == 64 and all(
        len(c) == 12 for c in doc["clients"])
    assert 64 <= prompts.min() and prompts.max() <= 7168
    assert 64 <= outputs.min() and outputs.max() <= 768
    assert 230 < np.median(outputs) < 300
    # 84% of the tokens are prompt tokens; documents over 4096 for
    # `correct` to find one
    assert 0.8 < prompts.sum() / (prompts.sum() + outputs.sum()) < 0.88
    assert doc["check_prompt_over"] == 4096 and (prompts > 4096).sum() > 40
    copy = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                        f"deck-sessions-{os.getpid()}.json")
    with open(path) as f, open(copy, "w") as g:
        g.write(f.read())
    try:
        subprocess.run([sys.executable, os.path.join(
            ROOT, "benchmark", "traffic", "make_deck_mix.py"), copy],
            check=True)
        with open(copy) as f:
            assert json.load(f)["clients"] == doc["clients"]
    finally:
        os.remove(copy)


def test_rehearsal_of_the_controls(monkeypatch, capsys):
    """``benchmark/controls_granite.py`` at toy size: the run itself is
    correct, and every control the configuration lists is refused."""
    from benchmark import controls_granite

    where = on_the_cpu(monkeypatch)
    rc = controls_granite.main(
        ["--workload", TOY, "--seed", str(2**31 + 11), "--seconds", "0.5"],
        **where)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    verdicts = {line.split(":")[0][len("# control "):]: line.split(": ")[1]
                for line in out if line.startswith("# control ")}
    assert verdicts["the run itself"].startswith("correct")
    must = load("benchmark", "tests", "rehearsal", "configs",
                "toy-granite.json")["reference_controls"]
    assert set(must) >= set(load(
        "benchmark", "configs",
        "granite-4.0-h-small.json")["reference_controls"])
    assert len(must) == 7 and all(
        verdicts[c].startswith("refused") for c in must)
    assert json.loads(out[-1]) == {"controls_ok": True, "failed": [],
                                   "must_refuse": must}


def test_the_benchmarks_reference_is_the_repositorys():
    """``benchmark/reference_granite.py`` is ``models/
    decoder_reference_granite.py`` but for where it imports the parts
    that are no model's own from."""
    with open(os.path.join(ROOT, "apex_tpu", "models",
                           "decoder_reference_granite.py")) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference_granite.py")) as f:
        theirs = f.read()
    assert ours.replace("from apex_tpu.models.decoder_reference import",
                        "from benchmark.reference_trinity import") == theirs
    assert ours != theirs
