"""The benchmark's additions for ``mellum2-12b-a2.5b``, rehearsed on the
CPU at toy size: the ``serve_mellum`` driver through ``run.py`` (a toy
configuration, deck and manifest under ``benchmark/tests/rehearsal/``),
the configuration file against the published widths, the windowed
calls' cost function, the deck, the controls, and the benchmark's copy
of the reference. The device check is stubbed here, in the test: the
benchmark itself refuses a CPU.
"""

import json
import os

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal")
CELL = "mellum2-12b-a2.5b.serve-codechat"


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
        REHEARSAL, "BENCHMARK.mellum.json"),
                data_dirs=[REHEARSAL, run.BENCH_DIR])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_mellum_driver(monkeypatch, capsys, trace,
                                        rehearsal_manifest):
    from benchmark import run

    where = on_the_cpu(monkeypatch, rehearsal_manifest(
        "BENCHMARK.mellum.json", "toy-mellum.toy-codechat", CELL))
    rc = run.main(["--workload", "toy-mellum.toy-codechat", "--seed",
                   str(2**31 + 11), "--seconds", "0.5", "--trace",
                   str(trace)], **where)
    notes = capsys.readouterr().out.strip().splitlines()
    line = json.loads(notes[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0, notes
    assert line["attempted"] > 0 and line["window_compilations"] == 0
    if trace:
        assert {"decode_step_ms", "batch_fill_pct", "window_gather_pct",
                "gather_live_pct",
                "pool_behind_window_pct"} <= set(line["metrics"])
        assert 0 < line["metrics"]["pool_behind_window_pct"]["value"] < 75
        # no device plane on the CPU: the trace readers return nothing
        assert not {"moe_expert_pct", "attention_roofline.window1k",
                    "prefill_call_ms.codechat",
                    "pool_relayout_pct"} & set(line["metrics"])
        assert any("drained: 0 block(s) held" in n for n in notes)
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "itl_p95_ms",
                                        "setup_s"}
    # a prompt past check_prompt_over was checked, every pair was held
    assert any("request" in n and "1280 of 1280 routed pairs" in n
               for n in notes), notes
    assert any("programs warmed" in n and "'decode_step', 4, 16, 5" in n
               for n in notes), notes


def test_step_sequence_does_not_depend_on_the_seed():
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_mellum

    config = load("benchmark", "tests", "rehearsal", "configs",
                  "toy-mellum.json")
    deck = load("benchmark", "tests", "rehearsal", "traffic",
                "toy-codechat.json")["clients"]
    cfg = serve_mellum.decoder_config(config)
    runs = [serve_mellum.reachable_programs(
        PatternDecoder(cfg), cfg, config["engine"], deck, seed,
        config["vocab_size"], 300) for seed in (1, 2**31 + 5)]
    assert runs[0][1] == runs[1][1] and len(runs[0][1]) > 300
    assert runs[0][2] is not None
    assert {len(k) for k in runs[0][0] if k[0] == "decode_step"} == {4}
    assert {len(k) for k in runs[0][0] if k[0] == "prefill_chunk"} == {5}


def test_the_cells_deck_reaches_a_dozen_programs():
    """The committed deck dealt dry to the engine at the cell's own
    settings: two decode programs (tables of 512 and 1024 blocks, the
    tail 80 at either), chunks of 512 and 1024, a dozen programs in
    all, the same for any seed."""
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_mellum

    config = load("benchmark", "configs", "mellum2-12b-a2.5b.json")
    traffic = load("benchmark", "traffic", "serve-codechat.json")
    cfg = serve_mellum.decoder_config(config)
    keys, sequence, ended = serve_mellum.reachable_programs(
        PatternDecoder(cfg), cfg, config["engine"], traffic["clients"], 7,
        config["vocab_size"],
        traffic["lead_in_steps"] + traffic["horizon_steps"])
    assert len(keys) == 12, keys
    assert {k[2] for k in keys if k[0] != "decode_step"} == {512, 1024}
    assert sorted(k for k in keys if k[0] == "decode_step") == [
        ("decode_step", 16, 512, 80), ("decode_step", 16, 1024, 80)]
    assert ended is not None and ended > traffic["lead_in_steps"]
    chunks = sum(k[0] != "decode_step" for k in sequence)
    decodes = sum(k[0] == "decode_step" for k in sequence)
    assert 0.05 < chunks / decodes < 0.15       # a chunk a step in ten


def test_configuration_keeps_every_published_width():
    config = load("benchmark", "configs", "mellum2-12b-a2.5b.json")
    manifest = load("BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
        "blob/main/config.json")
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}}}
    assert {k: config[k] for k in published} == published
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == period * 2
    assert config["mlp_layer_types"] == ["sparse"] * 8
    assert config["published"]["num_hidden_layers"] == 28
    # no expert and no vocabulary row is cut
    deployment = config["deployment"]
    assert deployment["chips_per_layer"] == 1
    assert deployment["held_experts"] == [0, 64] \
        and deployment["router_width"] == 64
    # every other key is the file's own: nothing of the source is
    # missing or renamed
    own = {"name", "source", "reduced", "published", "deployment",
           "assumed", "engine", "reference_tolerance_ulps",
           "reference_choice_band", "reference_excused_margin",
           "reference_excused_share_max", "reference_controls",
           "reference_tolerance_why"}
    assert set(config) - set(published) - own == {
        "num_hidden_layers", "layer_types", "mlp_layer_types"}
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_mellum

    cfg = serve_mellum.decoder_config(config)
    assert cfg.layers == ((("window", "experts"),) * 3
                          + (("full", "experts"),)) * 2
    assert cfg.held_experts is None and cfg.moe_cfg().held_range == (0, 64)
    full, window = cfg.rotary_of("full"), cfg.rotary_of("window")
    assert (window.theta, window.factor) == (500000.0, 1.0)
    assert (full.factor, full.original_max_position) == (16.0, 8192)
    assert full.attention_factor == pytest.approx(0.1 * np.log(16) + 1)
    shapes = jax.eval_shape(
        lambda k: PatternDecoder(cfg).init(k, np.zeros((1, 8), np.int32)),
        jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == 8 * 417_747_712 + 2 * 98304 * 2304 + 2304   # 7.59 GB bf16
    # the program's YaRN frequencies are the reference's, written out
    # apart from it: unscaled below pair 18, scaled by 16 from pair 35
    from benchmark import reference_mellum

    arch = serve_mellum.arch_of(config, cfg)
    want = reference_mellum.yarn_inv_freq(128, arch.yarn)
    plain = reference_mellum.inv_freq(128, 500000.0)
    np.testing.assert_allclose(np.asarray(full.inv_freq(128)), want,
                               rtol=2e-6)
    np.testing.assert_allclose(want[:19], plain[:19])
    np.testing.assert_allclose(want[35:], plain[35:] / 16)
    assert ((want[19:35] < plain[19:35])
            & (want[19:35] > plain[19:35] / 16)).all()


def test_the_manifest_only_gained_entries():
    manifest = load("BENCHMARK.json")
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {
        "name": CELL, "config": "mellum2-12b-a2.5b",
        "traffic": "serve-codechat", "chips": 1, "why": cell["why"]}
    # the fourth configuration and the sixth cell: later PRs append
    assert manifest["configs"][3]["name"] == "mellum2-12b-a2.5b"
    assert manifest["workloads"][5] == cell
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    reported = {m["name"] for m in manifest["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    per_layer = [m["name"] for m in manifest["per_layer"]
                 if CELL in m.get("workloads", ())]
    # last, PR 37's split of a call by part of the model (no mixer and
    # no dense MLP in this model)
    assert per_layer[-7:] == [
        "decode_call_ms.attention", "decode_call_ms.cache",
        "decode_call_ms.experts", "decode_call_ms.head",
        "decode_call_ms.unscoped", "chunk_call_ms.experts",
        "chunk_call_ms.head"]
    per_layer = per_layer[:-7]
    # PR 33's four, then what came as data since: PR 34's counter of
    # whole-pool copies, which Trinity's cell reports too
    assert per_layer[-5:] == [
        "prefill_call_ms.codechat", "attention_roofline.codechat",
        "attention_roofline.window1k", "pool_behind_window_pct",
        "pool_relayout_pct"]
    assert set(per_layer[:-5]) == {
        "decode_step_ms", "batch_fill_pct", "host_gap_ms",
        "host_gap_schedule_ms", "host_gap_build_ms", "host_gap_dispatch_ms",
        "host_gap_sync_ms", "engine_dispatches_per_step", "moe_expert_pct",
        "device_idle_pct.serve", "window_gather_pct", "gather_live_pct"}
    for name in per_layer:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json")), name
    for name in per_layer[-5:-1]:
        assert by_name[name]["workloads"] == [CELL]
    # a later cell's name is appended to a list, nothing else changes
    relayout = by_name["pool_relayout_pct"]
    assert relayout["workloads"][:2] == [
        "trinity-large-preview.serve-longmix", CELL]
    assert {k: v for k, v in relayout.items() if k != "workloads"} == {
        "name": "pool_relayout_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "Server", "moves": "serve_tok_s"}
    # the cells pool_copy_pct lists are the two this one does not
    assert not set(by_name["pool_copy_pct"]["workloads"]) & set(
        relayout["workloads"])


POOL = "bf16[8,9217,16,4,128]"
#: whole HLO instructions as a chip's trace names them, (text, ns)
TOY_OPS = {
    "relayout": (f"%copy.5850 = {POOL}{{4,0,3,2,1:T(8,128)(2,1)}} "
                 "copy(%state_k.1)", 3000),
    "slot_update": (f"%dynamic_update_slice_fusion.7 = {POOL}"
                    "{4,3,2,1,0:T(4,128)(2,1)} fusion(%state_k.1, %p)", 500),
    "prefetch": (f"%copy-start.3 = ({POOL}{{4,3,2,1,0}}, u32[]) "
                 "copy-start(%w)", 700),
    "small_copy": ("%copy.12 = bf16[16,4,128]{2,1,0:T(4,128)(2,1)} "
                   "copy(%k_new)", 800),
    # a chunk call's grouped product, 1024 rows x top-8 (since PR 36 a
    # decode call's experts are dense fusions and no custom call)
    "product": ("%ragged-dot-none.3 = bf16[8192,2304]{1,0} "
                "custom-call(%x, %w)", 5000),
}


@pytest.mark.parametrize("ops,want", [
    (("relayout", "slot_update", "prefetch", "small_copy", "product"),
     100 * 3000 / 10000),
    (("slot_update", "prefetch", "small_copy", "product"), 0.0),
    (("relayout", "relayout", "product"), 100 * 6000 / 11000),
], ids=["a-pool-shaped-copy", "none", "k-and-v"])
def test_pool_relayout_pct_counts_whole_pool_copies(tmp_path, ops, want):
    """The metric's specification through its reader on a toy trace:
    only a ``copy`` instruction of the pool's shape counts, not the
    in-place slot updates, an asynchronous copy's start, a copy of
    another shape or anything else; without a device plane the reader
    gives nothing."""
    import types

    from benchmark import trace_reduce
    from benchmark.readers import op_share

    spec = load("benchmark", "layer_metrics", "pool_relayout_pct.json")
    assert spec["reader"] == "op_share"
    events, at = [], 1000
    for name in ops:
        text, ns = TOY_OPS[name]
        events.append([text, at, ns, {}])
        at += ns + 100
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.traced", 0, at, {}]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events}]}]}))
    run = types.SimpleNamespace(
        reduced=trace_reduce.reduce(trace_reduce.from_json(str(path))),
        facts={"pool_blocks": 9217})
    assert op_share.read(spec["params"], run) == pytest.approx(want)
    nothing = types.SimpleNamespace(reduced=None, facts={})
    assert op_share.read(spec["params"], nothing) is None


def test_the_deck_is_what_its_generator_draws():
    import subprocess
    import sys

    path = os.path.join(ROOT, "benchmark", "traffic", "serve-codechat.json")
    doc = load("benchmark", "traffic", "serve-codechat.json")
    assert doc["drawn_from"] == {
        "generator_seed": 33, "clients": 16, "requests_per_client": 12,
        "prompt": {"dist": "mixture", "parts": [
            {"name": "repository", "p": 0.25, "dist": "lognormal",
             "median": 6144, "sigma": 0.25, "clip": [4096, 8192]},
            {"name": "turn", "p": 0.75, "dist": "lognormal",
             "median": 1024, "sigma": 0.6, "clip": [128, 4096]}]},
        "output": {"dist": "lognormal", "median": 384, "sigma": 0.5,
                   "clip": [96, 1024]}}
    assert (doc["lead_in_steps"], doc["trace_steps"]) == (400, 40)
    prompts = np.array([p for c in doc["clients"] for p, _ in c])
    outputs = np.array([o for c in doc["clients"] for _, o in c])
    assert len(doc["clients"]) == 16 and all(
        len(c) == 12 for c in doc["clients"])
    assert 128 <= prompts.min() and prompts.max() <= 8192
    assert 96 <= outputs.min() and outputs.max() <= 1024
    for c in doc["clients"]:             # a repository prompt in every list
        assert max(p for p, _ in c) >= 4096
    # decode does the steps: some 400 of them a request against a few
    # chunks, and every lane passes the 1024 window
    assert 300 < np.median(outputs) < 480
    assert (prompts + outputs > 1024).mean() > 0.6
    assert doc["check_prompt_over"] == 4096 < np.sort(prompts)[-16]
    copy = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                        f"deck-codechat-{os.getpid()}.json")
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


@pytest.mark.parametrize("q,k,want_pairs,want_keys", [
    # decode: 8 heads of a group against the 1280 gathered: the window
    ([64, 8, 128], [64, 1280, 128], 8 * 1024, 1024),
    # decode in a table narrower than the window: what the call holds
    ([64, 8, 128], [64, 512, 128], 8 * 512, 512),
    # a chunk of 1024 after a full tail: every query sees the window
    ([128, 1024, 128], [16, 2304, 128], 1024 * 1024, 2047),
    # a chunk of 512 over a short tail: the causal count, under the window
    ([32, 512, 128], [4, 768, 128],
     sum(min(1024, 256 + i + 1) for i in range(512)), 768),
])
def test_window_cost_counts_at_most_1024_keys_a_query(q, k, want_pairs,
                                                      want_keys):
    from benchmark import costs, costs_mellum

    assert costs_mellum.WINDOW == 1024
    operands = [("bf16", q), ("bf16", k), ("bf16", k), ("s32", [16, 1, k[1]])]
    results = [("bf16", q), ("f32", q[:2] + [1])]
    flops, nbytes = costs_mellum.attention_window_call(results, operands)
    heads = q[0]
    assert flops == 4.0 * heads * want_pairs * 128
    assert flops <= 4.0 * heads * q[1] * 1024 * 128
    fixed = 2 * 2 * np.prod(q) + 4 * np.prod(q[:2]) + 4 * 16 * k[1]
    assert nbytes == fixed + 2 * 2 * k[0] * want_keys * 128
    # and never more than the full layers' count of the same shapes
    full = costs.attention_call(results, operands)
    assert flops <= full[0] * 1.001 + 4.0 * heads * q[1] ** 2 * 128 \
        and nbytes <= full[1]


def test_rehearsal_of_the_controls(monkeypatch, capsys):
    """``benchmark/controls_mellum.py`` at toy size: the run itself is
    correct, and every control the configuration lists is refused."""
    from benchmark import controls_mellum

    where = on_the_cpu(monkeypatch)
    rc = controls_mellum.main(
        ["--workload", "toy-mellum.toy-codechat", "--seed", str(2**31 + 11),
         "--seconds", "0.5"], **where)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    verdicts = {line.split(":")[0][len("# control "):]: line.split(": ")[1]
                for line in out if line.startswith("# control ")}
    assert verdicts["the run itself"].startswith("correct")
    must = load("benchmark", "tests", "rehearsal", "configs",
                "toy-mellum.json")["reference_controls"]
    assert must == load("benchmark", "configs",
                        "mellum2-12b-a2.5b.json")["reference_controls"]
    assert len(must) == 4 and all(
        verdicts[c].startswith("refused") for c in must)
    assert json.loads(out[-1]) == {"controls_ok": True, "failed": [],
                                   "must_refuse": must}


def test_the_benchmarks_reference_is_the_repositorys():
    """``benchmark/reference_mellum.py`` is ``models/
    decoder_reference_mellum.py`` but for where it imports the parts
    that are no model's own from."""
    with open(os.path.join(ROOT, "apex_tpu", "models",
                           "decoder_reference_mellum.py")) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference_mellum.py")) as f:
        theirs = f.read()
    assert ours.replace("from apex_tpu.models.decoder_reference import",
                        "from benchmark.reference_trinity import") == theirs
    assert ours != theirs
