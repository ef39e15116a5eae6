"""The benchmark's additions for ``ai21-jamba2-3b``, rehearsed on the
CPU at toy size: the ``serve_jamba`` driver through ``run.py`` (a toy
configuration, deck and manifest under ``benchmark/tests/rehearsal/``),
its controls, the configuration file against the published widths, the
cell's arithmetic, the deck, the manifest's new entries, and the
benchmark's copy of the reference. The device check is stubbed here,
in the test: the benchmark itself refuses a CPU.
"""

import json
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal")
CELL = "ai21-jamba2-3b.serve-reasoning"
TOY = "toy-jamba.toy-reasoning"


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def on_the_cpu(monkeypatch, manifest_path):
    from benchmark import run

    monkeypatch.setattr(run, "accelerator", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(run, "peaks_for", lambda kind, dirs: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: None)
    return dict(manifest_path=manifest_path,
                data_dirs=[REHEARSAL, run.BENCH_DIR])


@pytest.mark.parametrize("how", ["untraced", "traced", "controls"])
def test_rehearsal_of_the_jamba_driver(monkeypatch, capsys, how,
                                       rehearsal_manifest):
    """The driver end to end at toy size: ``correct`` (two requests
    followed through the timed dispatches by their state slots, their
    logits held to the float32 reference), drained, no compile in the
    window; the traced run reports its metrics; the controls script
    refuses every wrong model it names."""
    from benchmark import controls_jamba, run

    where = on_the_cpu(monkeypatch, rehearsal_manifest(
        "BENCHMARK.jamba.json", TOY, CELL))
    argv = ["--workload", TOY, "--seed", str(2**31 + 21),
            "--seconds", "0.5"]
    if how == "controls":
        rc = controls_jamba.main(argv, **where)
    else:
        rc = run.main(argv + ["--trace", str(int(how == "traced"))],
                      **where)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    line = json.loads(next(n for n in out if n.startswith('{"correct"')))
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["attempted"] > 0 and line["window_compilations"] == 0
    assert any("drained: 0 block(s) and 0 state slot(s) held" in n
               for n in out), out
    checked = [n for n in out if n.startswith("# request")]
    assert len(checked) == 2 and all(
        "kept from the dispatches that served them" in n
        for n in checked), out
    if how == "traced":
        assert {"decode_step_ms", "batch_fill_pct",
                "gather_live_pct"} <= set(line["metrics"])
        # no device plane on the CPU: the trace readers return nothing
        assert not {"ssm_state_pct", "ssm_scan_roofline",
                    "ssm_step_roofline", "pool_relayout_pct",
                    "prefill_call_ms.reasoning"} & set(line["metrics"])
    if how == "controls":
        verdict = json.loads(out[-1])
        assert verdict["controls_ok"] is True and not verdict["failed"]
        refused = [n for n in out if n.startswith("# control ")
                   and ": refused;" in n]
        assert len(refused) == len(verdict["must_refuse"]) == 8, out


def test_step_sequence_does_not_depend_on_the_seed():
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_granite, serve_jamba

    config = load("benchmark", "tests", "rehearsal", "configs",
                  "toy-jamba.json")
    deck = load("benchmark", "tests", "rehearsal", "traffic",
                "toy-reasoning.json")["clients"]
    cfg = serve_jamba.decoder_config(config)
    runs = [serve_granite.reachable_programs(
        PatternDecoder(cfg), cfg, config["engine"], deck, seed,
        config["vocab_size"], 300) for seed in (1, 2**31 + 5)]
    assert runs[0][1] == runs[1][1] and len(runs[0][1]) > 300
    assert ([(at, req.id) for at, req in runs[0][2]]
            == [(at, req.id) for at, req in runs[1][2]]) and runs[0][2]


def test_the_cells_deck_reaches_five_programs():
    """The committed deck dealt dry to the engine at the cell's own
    settings: one decode program (256 lanes, tables of 256 blocks), a
    whole-prompt and a chunk program of 512 and of 1024 rows at one
    lane, five in all; a prefill-type call in about one step of four;
    the two requests the window follows end early in it (a turn of
    one whole-prompt prefill in a reused slot, a prompt of two chunks
    decoded ~950 steps beside 255 other lanes)."""
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_granite, serve_jamba

    config = load("benchmark", "configs", "ai21-jamba2-3b.json")
    traffic = load("benchmark", "traffic", "serve-reasoning.json")
    cfg = serve_jamba.decoder_config(config)
    lead = traffic["lead_in_steps"]
    keys, sequence, ended = serve_granite.reachable_programs(
        PatternDecoder(cfg), cfg, config["engine"], traffic["clients"], 7,
        config["vocab_size"], lead + 600)
    assert sorted(keys) == sorted(
        [("decode_step", 256, 256)]
        + [(fn, 1, s, 256) for fn in ("prefill_step", "prefill_chunk")
           for s in (512, 1024)])
    window = sequence[-1100:]            # dispatches past the lead-in
    prefills = sum(k[0] != "decode_step" for k in window)
    decodes = sum(k[0] == "decode_step" for k in window)
    assert 0.2 < prefills / decodes < 0.35
    follow = serve_granite.checked(
        [req for at, req in ended if at > lead]
        [:traffic["check_among_first"]], config["engine"]["prefill_chunk"],
        traffic["check_prompt_over"])
    assert len(follow) == 2
    turn, long = follow
    assert len(turn.prompt) <= 1024 and turn.id[1] >= 1
    assert 1024 < len(long.prompt) <= 2048 and long.max_new_tokens > 900
    ends = {req.id: at for at, req in ended}
    assert all(lead < ends[r.id] < lead + 200 for r in follow)


def test_configuration_keeps_every_published_width():
    config = load("benchmark", "configs", "ai21-jamba2-3b.json")
    manifest = load("BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "ai21-jamba2-3b")
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/"
        "config.json")
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert {k: config[k] for k in published} == published
    own = {"name", "source", "reduced", "published", "deployment",
           "assumed", "engine", "reference_tolerance_ulps",
           "reference_logit_error_max", "reference_logit_rms_max",
           "reference_controls",
           "reference_tolerance_why"}
    assert set(config) - set(published) == own
    for reason in ("in_proj_order", "inner_norms", "dt_bias", "state_dtype",
                   "init_why", "attention"):
        assert len(config["assumed"][reason]) > 40, reason
    from apex_tpu import serving
    from benchmark.drivers import serve_jamba

    cfg = serve_jamba.decoder_config(config)
    cache = serving.KVCache.for_config(
        cfg, num_blocks=config["engine"]["num_blocks"], block_size=16,
        state_slots=config["engine"]["state_slots"])
    # the paged pool has the two attention layers of one KV head; a
    # slot holds 26 layers of state
    assert cache.num_layers == 2 and cache.kv_heads == 1
    assert cache.slot_bytes() == 26 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert cache.row_bytes() == 2 * 2 * 128 * 2


def test_the_cells_arithmetic():
    """``costs_jamba`` against the numbers the cell's ``why`` and
    ``PERF.md`` quote."""
    from benchmark import costs_jamba

    config = load("benchmark", "configs", "ai21-jamba2-3b.json")
    assert costs_jamba.session_state_bytes(config) == 9_318_400
    assert costs_jamba.kv_bytes_per_token(config) == 1024
    p = costs_jamba.parameters(config)
    assert p["mamba"] == pytest.approx(41.24e6, rel=0.001)
    assert p["attention"] + p["mlp"] == pytest.approx(76.68e6, rel=0.001)
    assert p["total"] == pytest.approx(3.029e9, rel=0.001)
    step = costs_jamba.decode_step_bytes(config, 256)
    assert step["state"] == pytest.approx(4.77e9, rel=0.01)
    assert step["weights"] == pytest.approx(6.06e9, rel=0.01)
    engine = config["engine"]
    # every lane at max_context fits the pool; the slots and the pool
    assert (engine["num_blocks"] * engine["block_size"]
            >= engine["max_batch"] * engine["max_context"])
    assert (engine["state_slots"] + 1) * 9_318_400 == pytest.approx(
        2.39e9, rel=0.01)


def test_the_manifest_only_gained_entries():
    """The configuration and the cell at the ends of their lists, the
    cell appended to the lists of the metrics it reports, no per-layer
    entry of its own (the thirteen scope metrics stay last)."""
    manifest = load("BENCHMARK.json")
    i = next(i for i, w in enumerate(manifest["workloads"])
             if w["name"] == CELL)
    assert manifest["workloads"][i] == {
        "name": CELL, "config": "ai21-jamba2-3b",
        "traffic": "serve-reasoning", "chips": 1,
        "why": manifest["workloads"][i]["why"]}
    assert [c["name"] for c in manifest["configs"]].index(
        "ai21-jamba2-3b") == 6 and i == 8
    later = {w["name"] for w in manifest["workloads"][i + 1:]}
    reported = {m["name"] for m in manifest["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    per_layer = [m for m in manifest["per_layer"]
                 if CELL in m.get("workloads", ())]
    assert {m["name"] for m in per_layer} == {
        "decode_step_ms", "batch_fill_pct", "device_idle_pct.serve",
        "engine_dispatches_per_step", "host_gap_ms", "host_gap_schedule_ms",
        "host_gap_build_ms", "host_gap_dispatch_ms", "host_gap_sync_ms",
        "gather_live_pct", "pool_relayout_pct", "ssm_state_pct",
        "ssm_state_copy_pct", "ssm_step_roofline",
        "decode_call_ms.attention", "decode_call_ms.cache",
        "decode_call_ms.mixer", "decode_call_ms.mlp", "decode_call_ms.head",
        "decode_call_ms.unscoped", "chunk_call_ms.head"}
    for m in manifest["end_to_end"] + per_layer:
        if CELL in m.get("workloads", ()):
            after = m["workloads"][m["workloads"].index(CELL) + 1:]
            assert set(after) <= later, m["name"]
    # the cell's own two metrics wait outside the manifest beside the
    # others that S9 item 14 of ROADMAP.md keeps out (PERF.md section 7
    # (13))
    names = {m["name"] for m in manifest["per_layer"]}
    for name in ("ssm_scan_roofline", "prefill_call_ms.reasoning"):
        assert name not in names
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))


def test_the_deck_is_what_its_generator_draws():
    import numpy as np

    from benchmark.traffic import make_deck

    traffic = load("benchmark", "traffic", "serve-reasoning.json")
    assert traffic["clients"] == make_deck.make(traffic["drawn_from"])
    pairs = np.asarray(traffic["clients"]).reshape(-1, 2)
    assert pairs.shape == (256 * 12, 2)
    assert 0.33 < pairs[:, 0].sum() / pairs.sum() < 0.40
    assert pairs.sum(1).max() <= load(
        "benchmark", "configs", "ai21-jamba2-3b.json")["engine"][
        "max_context"]


def test_the_benchmarks_reference_is_the_repositorys():
    """``benchmark/reference_jamba.py`` is ``models/
    decoder_reference_jamba.py`` but for where it imports the parts no
    model owns."""
    with open(os.path.join(ROOT, "apex_tpu", "models",
                           "decoder_reference_jamba.py")) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference_jamba.py")) as f:
        theirs = f.read()
    assert theirs == ours.replace("from apex_tpu.models.decoder_reference "
                                  "import (",
                                  "from benchmark.reference_trinity import (")
