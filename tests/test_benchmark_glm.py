"""The benchmark's additions for ``glm-4.7-flash``, rehearsed on the CPU
at toy size: the ``serve_glm`` driver through ``run.py`` and through
its controls (a toy configuration, deck and manifest under
``benchmark/tests/rehearsal/``), the configuration file against the
published widths, the cut's arithmetic and its kernel's price, the
pools' bytes a token by their own rows, the deck, the manifest, and
the benchmark's copy of the reference. The device check is stubbed
here, in the test: the benchmark itself refuses a CPU.
"""

import json
import os

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal")
CELL = "glm-4.7-flash.serve-agent"
TOY = "toy-glm.toy-agent"


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


@pytest.mark.parametrize("how", ["traced", "controls"])
def test_rehearsal_of_the_glm_driver(monkeypatch, capsys, how,
                                     rehearsal_manifest):
    """The cell's driver at toy size, traced through ``run.py`` and
    untraced through ``benchmark/controls_glm.py`` (which runs the cell
    and then judges what it served again under every control): the run
    is correct with no failed request and no compile in the window, the
    traced run drains to no block, the two requests followed are the two
    checked, by the logits the window's own dispatches computed, and
    every control the configuration lists is refused."""
    from benchmark import controls_glm, run

    where = on_the_cpu(monkeypatch, rehearsal_manifest(
        "BENCHMARK.glm.json", TOY, CELL))
    argv = ["--workload", TOY, "--seed", str(2**31 + 11), "--seconds", "2"]
    if how == "traced":
        rc = run.main(argv + ["--trace", "1"], **where)
    else:
        rc = controls_glm.main(argv, **where)
    notes = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, notes
    line = json.loads(next(n for n in notes if n.startswith("{\"correct")))
    assert line["correct"] is True and line["failed"] == 0, notes
    assert line["attempted"] > 0 and line["window_compilations"] == 0
    if how == "traced":
        assert {"decode_step_ms", "batch_fill_pct",
                "gather_live_pct"} <= set(line["metrics"])
        # no device plane on the CPU: the trace readers return nothing
        assert not {"mla_decode_roofline", "attention_roofline.agent",
                    "prefill_call_ms.agent",
                    "pool_relayout_pct"} & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "itl_p95_ms",
                                        "setup_s"}
        verdicts = {n.split(":")[0][len("# control "):]: n.split(": ")[1]
                    for n in notes if n.startswith("# control ")}
        must = load("benchmark", "tests", "rehearsal", "configs",
                    "toy-glm.json")["reference_controls"]
        assert set(must) == set(load(
            "benchmark", "configs", "glm-4.7-flash.json")[
                "reference_controls"])
        assert all(verdicts[c].startswith("refused") for c in must), notes
        assert json.loads(notes[-1]) == {"controls_ok": True, "failed": [],
                                         "must_refuse": must}
    # a traced run drains the engine, and then holds no block
    assert any("drained: 0 block(s) held" in n
               for n in notes) == (how == "traced"), notes
    checked = [n for n in notes if n.startswith("# request")]
    assert len(checked) == 2 and all(
        "kept from the dispatches that served them" in n
        for n in checked), notes
    assert any("followed for their logits: (" in n for n in notes), notes


def test_step_sequence_does_not_depend_on_the_seed():
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_glm

    config = load("benchmark", "tests", "rehearsal", "configs",
                  "toy-glm.json")
    deck = load("benchmark", "tests", "rehearsal", "traffic",
                "toy-agent.json")["clients"]
    cfg = serve_glm.decoder_config(config)
    runs = [serve_glm.reachable_programs(
        PatternDecoder(cfg), cfg, config["engine"], deck, seed,
        config["vocab_size"], 300) for seed in (1, 2**31 + 5)]
    assert runs[0][1] == runs[1][1] and len(runs[0][1]) > 300
    assert ([(at, req.id) for at, req in runs[0][2]]
            == [(at, req.id) for at, req in runs[1][2]]) and runs[0][2]
    # no window layer: no second table in any key
    assert {len(k) for k in runs[0][0] if k[0] == "decode_step"} == {3}
    assert {len(k) for k in runs[0][0] if k[0] != "decode_step"} == {4}


PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1,
    "num_key_value_heads": 20, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "vocab_size": 154880}


def test_configuration_keeps_every_published_width():
    config = load("benchmark", "configs", "glm-4.7-flash.json")
    entry = next(c for c in load("BENCHMARK.json")["configs"]
                 if c["name"] == "glm-4.7-flash")
    reduced = ["num_hidden_layers", "n_routed_experts",
               "num_nextn_predict_layers"]
    assert entry["reduced"] == config["reduced"] == reduced
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert [config[k] for k in reduced] == [6, 8, 0]
    assert [config["published"][k] for k in reduced] == [47, 64, 1]
    deployment = config["deployment"]
    assert deployment["chips_per_layer"] == 8
    assert deployment["held_experts"] == [0, 8] \
        and deployment["router_width"] == 64
    own = {"name", "source", "reduced", "published", "deployment",
           "assumed", "engine", "reference_tolerance_ulps",
           "reference_choice_band", "reference_excused_margin",
           "reference_excused_share_max", "reference_logit_error_max",
           "reference_controls", "reference_tolerance_why"}
    assert set(config) - set(PUBLISHED) - own == set(reduced)
    for reason in ("rope_layout", "router", "shared_expert", "latent_row",
                   "init_why"):
        assert len(config["assumed"][reason]) > 40, reason
    from apex_tpu import serving
    from benchmark.drivers import serve_glm

    cfg = serve_glm.decoder_config(config)
    assert cfg.layers == (("latent", "dense"),) + (("latent", "experts"),) * 5
    assert cfg.moe_cfg().held_range == (0, 8) and cfg.num_experts == 64
    assert (cfg.route_scale, cfg.router, cfg.norms) == (1.8, "sigmoid",
                                                        "pre")
    assert cfg.mla.row_width == 640 and cfg.head_dim == 256
    cache = serving.KVCache.for_config(
        cfg, num_blocks=config["engine"]["num_blocks"], block_size=16)
    assert (cache.num_layers, cache.kv_heads, cache.head_dim,
            cache.num_pools) == (6, 1, 640, 1)


def test_the_cells_arithmetic():
    """``costs_glm`` against the numbers the configuration's ``why``
    and ``PERF.md`` quote."""
    from benchmark import costs_glm

    config = load("benchmark", "configs", "glm-4.7-flash.json")
    assert costs_glm.row_width(config) == 640
    assert costs_glm.latent_bytes_per_token(config) == 7680
    assert costs_glm.pool_bytes(config) == pytest.approx(8.05e9, rel=0.001)
    p = costs_glm.parameters(config)
    assert p["attention"] == pytest.approx(21.76e6, rel=0.001)
    assert p["dense"] == pytest.approx(62.9e6, rel=0.001)
    assert p["expert"] == p["shared"] == 3 * 2048 * 1536
    assert p["vocabulary"] == pytest.approx(634.4e6, rel=0.001)
    # the cut: 2.51 GB of bf16, every parameter the program holds
    assert p["total"] == 1253214016
    whole = dict(config, num_hidden_layers=47, n_routed_experts=64)
    assert costs_glm.parameters(whole)["total"] == pytest.approx(
        29.94e9, rel=0.001)
    step = costs_glm.decode_step_bytes(config, 32, 4096)
    weights = step["attention_weights"] + step["mlp_weights"] + step["head"]
    assert weights == pytest.approx(1.87e9, rel=0.01)
    assert step["latents"] == pytest.approx(16.1e9, rel=0.01)


def test_each_pool_counts_its_own_row():
    """Every count of a pool's bytes a token reads the pool's own row:
    each GQA cell's K and V of ``kv_heads x head_dim`` as before, and
    the latent cell's one pool of rows of 640."""
    from apex_tpu import serving
    from apex_tpu.models.gpt import GPTConfig
    from benchmark.drivers import (serve_glm, serve_granite, serve_mellum,
                                   serve_pattern)

    made = {"trinity-large-preview": serve_pattern,
            "mellum2-12b-a2.5b": serve_mellum,
            "granite-4.0-h-small": serve_granite,
            "glm-4.7-flash": serve_glm}
    # bytes a token over the pool's layers: what PERF.md and the cells'
    # arithmetic have quoted since they were added
    want = {"trinity-large-preview": 5 * 2 * 8 * 128 * 2,
            "mellum2-12b-a2.5b": 8 * 2 * 4 * 128 * 2,
            "granite-4.0-h-small": 1 * 2 * 8 * 128 * 2,
            "glm-4.7-flash": 6 * 640 * 2,
            "cerebras-gpt-1.3b": 24 * 2 * 2048 * 2}
    for name, drv in made.items():
        config = load("benchmark", "configs", name + ".json")
        cfg = drv.decoder_config(config)
        cache = serving.KVCache.for_config(
            cfg, num_blocks=64, block_size=16,
            state_slots=2 if cfg.mamba is not None else 0)
        assert cache.row_bytes() == want[name], name
        assert cache.block_bytes() == 16 * want[name]
        assert cache.pool_bytes() == 65 * 16 * want[name]
        state = jax.eval_shape(cache.init_state)
        assert len(state.pools) == (1 if name == "glm-4.7-flash" else 2)
        assert sum(np.prod(p.shape) * p.dtype.itemsize
                   for p in state.pools) == cache.pool_bytes(), name
    gpt = load("benchmark", "configs", "cerebras-gpt-1.3b.json")
    cache = serving.KVCache.for_config(GPTConfig(
        vocab_size=gpt["vocab_size"], max_seq_len=gpt["n_positions"],
        hidden_size=gpt["n_embd"], num_layers=gpt["n_layer"],
        num_heads=gpt["n_head"]), num_blocks=64, block_size=16,
        dtype=jax.numpy.bfloat16)
    assert cache.row_bytes() == want["cerebras-gpt-1.3b"]


def test_the_manifest_only_gained_entries():
    manifest = load("BENCHMARK.json")
    cell = manifest["workloads"][-1]
    assert cell == {"name": CELL, "config": "glm-4.7-flash",
                    "traffic": "serve-agent", "chips": 1, "why": cell["why"]}
    assert manifest["configs"][-1]["name"] == "glm-4.7-flash"
    assert len(manifest["configs"]) == 6 and len(manifest["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    reported = {m["name"] for m in manifest["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    # the cell's own three metrics are read by the rehearsal's manifest
    # and not by this one: benchmark/tests/test_scope_time.py holds the
    # thirteen scope metrics to be the manifest's last entries, and an
    # entry put before them reads as a change to what was there
    names = [m["name"] for m in manifest["per_layer"]]
    assert len(names) == 49 and names[-13] == "decode_call_ms.attention"
    own = ["mla_decode_roofline", "attention_roofline.agent",
           "prefill_call_ms.agent"]
    assert not set(own) & set(names)
    for name in own:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == {
        "decode_step_ms", "batch_fill_pct", "host_gap_ms",
        "host_gap_schedule_ms", "host_gap_build_ms", "host_gap_dispatch_ms",
        "host_gap_sync_ms", "engine_dispatches_per_step",
        "device_idle_pct.serve", "gather_live_pct", "pool_relayout_pct",
        "decode_call_ms.attention",
        "decode_call_ms.cache", "decode_call_ms.experts",
        "decode_call_ms.mlp", "decode_call_ms.head",
        "decode_call_ms.unscoped", "chunk_call_ms.experts",
        "chunk_call_ms.head"}
    # a list that gained the cell gained it at its end
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]


def test_the_kernels_price():
    """``costs_glm.mla_decode_call`` from a call's shapes as a trace's
    HLO text gives them: 32 lanes of 65536 rows of 640, every row read
    once, and 2 x 20 x (512 + 64 + 512) operations a row."""
    from benchmark import costs_glm

    flops, nbytes = costs_glm.mla_decode_call(
        [("f32", [32, 20, 512])],
        [("s32", [32]), ("bf16", [32, 20, 640]),
         ("bf16", [32, 1, 65536, 640])])
    assert flops == 2.0 * 20 * 32 * 65536 * 1088
    assert nbytes == (32 * 65536 * 640 * 2 + 32 * 20 * 640 * 2
                      + 32 * 20 * 512 * 4 + 32 * 4)


def test_the_deck_is_what_its_generator_draws():
    import subprocess
    import sys

    path = os.path.join(ROOT, "benchmark", "traffic", "serve-agent.json")
    doc = load("benchmark", "traffic", "serve-agent.json")
    assert doc["drawn_from"] == {
        "generator_seed": 40, "clients": 32, "requests_per_client": 12,
        "prompt": {"dist": "mixture", "parts": [
            {"name": "repository", "p": 0.25, "dist": "lognormal",
             "median": 24576, "sigma": 0.2, "clip": [16384, 32768]},
            {"name": "turn", "p": 0.75, "dist": "lognormal",
             "median": 4096, "sigma": 0.6, "clip": [512, 12288]}]},
        "output": {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                   "clip": [256, 2048]}}
    assert doc["driver"] == "serve_glm"
    prompts = np.array([p for c in doc["clients"] for p, _ in c])
    outputs = np.array([o for c in doc["clients"] for _, o in c])
    assert len(doc["clients"]) == 32 and all(
        len(c) == 12 for c in doc["clients"])
    assert 512 <= prompts.min() and prompts.max() <= 32768
    assert 256 <= outputs.min() and outputs.max() <= 2048
    assert doc["check_prompt_over"] == 12288 and (prompts > 12288).sum() > 60
    copy = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                        f"deck-agent-{os.getpid()}.json")
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


def test_the_benchmarks_reference_is_the_repositorys():
    """``benchmark/reference_glm.py`` is ``models/decoder_reference_glm.py``
    but for where it imports the parts that are no model's own from."""
    with open(os.path.join(ROOT, "apex_tpu", "models",
                           "decoder_reference_glm.py")) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference_glm.py")) as f:
        theirs = f.read()
    assert ours.replace("from apex_tpu.models.decoder_reference import",
                        "from benchmark.reference_trinity import") == theirs
    assert ours != theirs
