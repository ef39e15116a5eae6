"""The benchmark's additions for the pattern decoder, rehearsed on the
CPU at toy size: the ``serve_pattern`` driver through ``run.py`` (a toy
configuration, traffic mix and manifest under
``benchmark/tests/rehearsal/``), the configuration file against the
catalog's widths, the windowed calls' cost function, and the deck.
The device check is stubbed here, in the test: the benchmark itself
refuses a CPU.
"""

import json
import os

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture
def bench(monkeypatch, rehearsal_manifest):
    from benchmark import run

    monkeypatch.setattr(run, "accelerator", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(run, "peaks_for", lambda kind, dirs: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: None)

    def go(capsys, *argv):
        rc = run.main(list(argv), manifest_path=rehearsal_manifest(
            "BENCHMARK.trinity.json", "toy-trinity.toy-longmix",
            "trinity-large-preview.serve-longmix"),
            data_dirs=[REHEARSAL, run.BENCH_DIR])
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()
        return json.loads(out[-1]), out

    return go


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_pattern_driver(bench, capsys, trace):
    line, notes = bench(capsys, "--workload", "toy-trinity.toy-longmix",
                        "--seed", str(2**31 + 11), "--seconds", "0.5",
                        "--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0, notes
    assert line["attempted"] > 0 and line["window_compilations"] == 0
    if trace:
        assert {"decode_step_ms", "batch_fill_pct",
                "window_gather_pct"} <= set(line["metrics"])
        assert 0 < line["metrics"]["window_gather_pct"]["value"] < 100
        # no device plane on the CPU: the trace readers return nothing
        assert not {"moe_expert_pct",
                    "pool_relayout_pct"} & set(line["metrics"])
        assert any("drained: 0 block(s) held" in n for n in notes)
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "itl_p95_ms",
                                        "setup_s"}
    assert any("programs warmed" in n and "'decode_step', 4, 16, 5" in n
               for n in notes), notes


def test_step_sequence_does_not_depend_on_the_seed():
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_pattern

    config = load("benchmark", "tests", "rehearsal", "configs",
                  "toy-trinity.json")
    deck = load("benchmark", "tests", "rehearsal", "traffic",
                "toy-longmix.json")["clients"]
    cfg = serve_pattern.decoder_config(config)
    runs = [serve_pattern.reachable_programs(
        PatternDecoder(cfg), cfg, config["engine"], deck, seed,
        config["vocab_size"], 300) for seed in (1, 2**31 + 5)]
    assert runs[0][1] == runs[1][1] and len(runs[0][1]) > 300
    assert runs[0][2] is not None
    # every dispatch over the cache carries both widths
    assert {len(k) for k in runs[0][0] if k[0] == "decode_step"} == {4}
    assert {len(k) for k in runs[0][0] if k[0] == "prefill_chunk"} == {5}


def test_configuration_keeps_every_published_width():
    config = load("benchmark", "configs", "trinity-large-preview.json")
    manifest = load("BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "trinity-large-preview")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    published = {
        "hidden_size": 3072, "intermediate_size": 12288, "head_dim": 128,
        "moe_intermediate_size": 3072, "num_attention_heads": 48,
        "num_key_value_heads": 8, "num_experts_per_tok": 4,
        "num_shared_experts": 1, "sliding_window": 4096,
        "route_scale": 2.448, "rms_norm_eps": 1e-05, "rope_theta": 10000}
    assert {k: config[k] for k in published} == published
    assert config["published"]["num_experts"] == 256 \
        == config["deployment"]["router_width"]
    assert config["deployment"]["held_experts"] == [0, config["num_experts"]]
    assert config["vocab_size"] * config["deployment"]["chips_per_layer"] \
        == config["published"]["vocab_size"]
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_pattern

    cfg = serve_pattern.decoder_config(config)
    assert cfg.layers == (("window", "dense"),) + (("window", "experts"),) * 3 \
        + (("full", "experts"),)
    shapes = jax.eval_shape(
        lambda k: PatternDecoder(cfg).init(k, np.zeros((1, 8), np.int32)),
        jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert 4.30e9 < n < 4.34e9          # 8.64 GB in bf16


def test_the_deck_is_what_its_generator_draws():
    import subprocess
    import sys

    path = os.path.join(ROOT, "benchmark", "traffic", "serve-longmix.json")
    doc = load("benchmark", "traffic", "serve-longmix.json")
    prompts = np.array([p for c in doc["clients"] for p, _ in c])
    outputs = np.array([o for c in doc["clients"] for _, o in c])
    assert len(doc["clients"]) == 16 and all(
        len(c) == 12 for c in doc["clients"])
    for c in doc["clients"]:             # short and long in every list
        assert min(p for p, _ in c) <= 2048 and max(p for p, _ in c) >= 4608
    assert ((prompts >= 4608) | (prompts <= 2048)).all()
    assert prompts.max() <= 10240 and 48 <= outputs.min() \
        and outputs.max() <= 512
    assert prompts.sum() / (prompts.sum() + outputs.sum()) > 0.9
    copy = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                        f"deck-{os.getpid()}.json")
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
    # decode: 6 heads of a group against the 5120 gathered: the window
    ([128, 6, 128], [128, 5120, 128], 6 * 4096, 4096),
    # decode in a narrow table: never more than the call holds
    ([128, 6, 128], [128, 2048, 128], 6 * 2048, 2048),
    # a chunk of 1024 after a full tail: every query sees the window
    ([192, 1024, 128], [32, 6144, 128], 1024 * 4096, 5119),
    # a chunk over a short tail: the causal count, under the window
    ([192, 512, 128], [32, 1024, 128],
     sum(512 + i + 1 for i in range(512)), 1024),
])
def test_window_cost_counts_at_most_the_window(q, k, want_pairs, want_keys):
    from benchmark import costs, costs_trinity

    operands = [("bf16", q), ("bf16", k), ("bf16", k), ("s32", [16, 1, k[1]])]
    results = [("bf16", q), ("f32", q[:2] + [1])]
    flops, nbytes = costs_trinity.attention_window_call(results, operands)
    heads = q[0]
    assert flops == 4.0 * heads * want_pairs * 128
    fixed = 2 * 2 * np.prod(q) + 4 * np.prod(q[:2]) + 4 * 16 * k[1]
    assert nbytes == fixed + 2 * 2 * k[0] * want_keys * 128
    # and never more than the full layer's count of the same shapes
    full = costs.attention_call(results, operands)
    assert flops <= full[0] * 1.001 + 4.0 * heads * q[1] ** 2 * 128 \
        and nbytes <= full[1]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_2x2_training_cell(bench, capsys, monkeypatch,
                                            trace):
    """The train driver through a 2x2 mesh of four (virtual) devices,
    as ``gpt2-medium.train-2x2`` runs it on four chips."""
    from benchmark import run

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    monkeypatch.setattr(run, "accelerator", lambda chips: jax.devices()[:4])
    line, notes = bench(capsys, "--workload", "toy-gpt.toy-train-2x2",
                        "--seed", "7", "--seconds", "0.5",
                        "--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0, notes
    assert line["device"]["count"] >= 4
    # the mesh driver's own look at the exchange: the step's change of
    # the parameters is the reference's, and one data shard's gradient
    # alone (the sum over the batch axis left out) is far from it
    note = next(n for n in notes if "one step from the seed's weights" in n)
    gap, unsummed = (float(note.split(marker)[1].split()[0].rstrip(";"))
                     for marker in ("to within ", "it reads "))
    assert gap < 0.01 < 0.5 < unsummed, note
    if trace:
        assert "train_step_ms" in line["metrics"]
        assert "collective_exposed_pct" not in line["metrics"]  # no device plane
    else:
        assert set(line["metrics"]) == {"train_tok_s", "setup_s"}


def test_the_2x2_traffic_is_the_one_chip_traffic_on_a_mesh():
    one = load("benchmark", "traffic", "train.json")
    four = load("benchmark", "traffic", "train-2x2.json")
    assert four["chips"] == 4 and four["mesh"] == {"batch": 2, "model": 2}
    assert four["batch"] == 8 and four["driver"] == "train_mesh"
    assert 0 < four["step_tolerance"] < 1
    same = set(one) - {"chips", "batch", "batch_why", "why", "who", "driver"}
    assert {k: four[k] for k in same} == {k: one[k] for k in same}


def test_a_gradient_not_summed_over_the_batch_axis_is_not_correct(
        bench, capsys, monkeypatch):
    """``train_mesh``'s check refuses what ``train``'s passes: a step
    that applies one data shard's gradient alone. The program is made
    to do so from outside, by handing every shard the first shard's
    rows while the reference keeps the true batch."""
    from benchmark import run
    from benchmark.drivers import train_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    monkeypatch.setattr(run, "accelerator", lambda chips: jax.devices()[:4])
    one_step = train_mesh.one_step

    def unsummed(cfg, optimizer, mesh, devices, params, tokens, labels):
        first = np.tile(np.arange(len(tokens) // mesh["batch"]),
                        mesh["batch"])
        return one_step(cfg, optimizer, mesh, devices, params,
                        tokens[first], labels[first])

    monkeypatch.setattr(train_mesh, "one_step", unsummed)
    line, notes = bench(capsys, "--workload", "toy-gpt.toy-train-2x2",
                        "--seed", "7", "--seconds", "0.3", "--trace", "0")
    assert line["correct"] is False and line["failed"] == 0, notes
    # train.py's own check saw nothing
    assert any("first" in n and "reference" in n and "tolerance" in n
               for n in notes)


def test_rehearsal_of_the_controls(monkeypatch, capsys):
    """``benchmark/controls_trinity.py`` at toy size: the run itself is
    correct, and every control the configuration lists is refused."""
    from benchmark import controls_trinity, run

    monkeypatch.setattr(run, "accelerator", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(run, "peaks_for", lambda kind, dirs: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: None)
    rc = controls_trinity.main(
        ["--workload", "toy-trinity.toy-longmix", "--seed", str(2**31 + 11),
         "--seconds", "0.5"],
        manifest_path=os.path.join(REHEARSAL, "BENCHMARK.trinity.json"),
        data_dirs=[REHEARSAL, run.BENCH_DIR])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    verdicts = {line.split(":")[0][len("# control "):]: line.split(": ")[1]
                for line in out if line.startswith("# control ")}
    assert verdicts["the run itself"].startswith("correct")
    must = load("benchmark", "tests", "rehearsal", "configs",
                "toy-trinity.json")["reference_controls"]
    assert must and all(verdicts[c].startswith("refused") for c in must)
    assert json.loads(out[-1]) == {"controls_ok": True, "failed": [],
                                   "must_refuse": must}


def test_the_reference_step_is_the_plain_gradient_and_adamw():
    """``reference_step.gradient`` (a block at a time) is ``jax.grad``
    of the plain loss written out whole; the first AdamW step is
    ``-lr * (sign-like g + wd * p)``; ``change_gap`` reads 0 for the
    same change and 1 for none."""
    import jax.numpy as jnp

    from benchmark import common, reference, reference_step

    cfg = common.gpt_config(load("benchmark", "tests", "rehearsal",
                                 "configs", "toy-gpt.json"))
    params = common.init_params(cfg, 3)
    toks = common.zipf_tokens(np.random.default_rng(0), (3, 33), 500)
    tokens, labels = toks[:, :-1], toks[:, 1:]

    def whole(params):
        with jax.default_matmul_precision("highest"):
            total = 0.0
            for t, lab in zip(tokens, labels):
                x = reference.hidden(params, t[None], heads=cfg.num_heads)[0]
                lg = x @ params["params"]["embedding"]["embedding"].T
                total += (jax.scipy.special.logsumexp(lg, -1)
                          - lg[jnp.arange(len(lab)), lab]).sum()
            return total / labels.size

    want_loss, want = jax.value_and_grad(whole)(params)
    loss, grad = reference_step.gradient(params, tokens, labels,
                                         heads=cfg.num_heads)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert jax.tree.structure(grad) == jax.tree.structure(params)
    for got, ref in zip(jax.tree.leaves(grad), jax.tree.leaves(want)):
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-5 * float(
            jnp.abs(ref).max() + 1e-30))
    change = jax.device_get(reference_step.first_adamw_change(
        params, grad, lr=1e-3, eps=1e-8, weight_decay=0.01))
    p, g, c = (np.asarray(jax.tree.leaves(t)[0])
               for t in (params, grad, change))
    np.testing.assert_allclose(
        c, -1e-3 * (g / (np.abs(g) + 1e-8) + 0.01 * p), rtol=1e-5, atol=1e-9)
    none = jax.tree.map(np.zeros_like, change)
    assert reference_step.change_gap(change, change) == 0.0
    assert reference_step.change_gap(none, change) == pytest.approx(1.0)
