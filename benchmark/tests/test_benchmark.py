"""Rehearsals of the benchmark on the CPU, and the trace reduction
against a fixture whose numbers were worked out by hand.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

The rehearsal cells live under ``rehearsal/``: a toy configuration, two
traffic mixes and a manifest of their own, found by the same ``run.py``
— adding them edited no file of the benchmark. The device check is
stubbed here, in the test: the benchmark itself refuses a CPU.
"""

import json
import os
import re
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal")


@pytest.fixture
def bench(monkeypatch):
    import jax

    from benchmark import run

    monkeypatch.setattr(run, "accelerator", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(run, "peaks_for", lambda kind, dirs: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})

    def go(capsys, *argv):
        rc = run.main(list(argv),
                      manifest_path=os.path.join(REHEARSAL, "BENCHMARK.json"),
                      data_dirs=[REHEARSAL, run.BENCH_DIR])
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go


CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell,metrics", [
    ("toy-gpt.toy-train", {"train_tok_s", "setup_s"}),
    ("toy-gpt.toy-serve", {"serve_tok_s", "itl_p95_ms", "ttft_iqm_ms",
                           "setup_s"}),
])
def test_run_end_to_end(bench, capsys, cell, metrics):
    line = bench(capsys, "--workload", cell, "--seed", str(2**31 + 11),
                 "--seconds", "0.5", "--trace", "0")
    assert CONTRACT_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["window_compilations"] == 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 and m["unit"] for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])


def test_traced_run_reports_per_layer_metrics(bench, capsys):
    line = bench(capsys, "--workload", "toy-gpt.toy-serve", "--seed", "5",
                 "--seconds", "0.5", "--trace", "1")
    assert line["correct"] is True          # drained: the pool is empty
    assert {"decode_step_ms", "batch_fill_pct"} <= set(line["metrics"])
    # a reader that finds no device plane (the CPU) returns nothing
    assert "device_idle_pct.serve" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_step_sequence_does_not_depend_on_the_seed():
    from apex_tpu.models.gpt import GPTModel
    from benchmark import common
    from benchmark.drivers import serve_closed

    with open(os.path.join(REHEARSAL, "configs", "toy-gpt.json")) as f:
        config = json.load(f)
    with open(os.path.join(REHEARSAL, "traffic", "toy-serve.json")) as f:
        deck = json.load(f)["clients"]
    cfg = common.gpt_config(config)
    runs = [serve_closed.reachable_programs(
        GPTModel(cfg), cfg, config["engine"], deck, seed,
        config["vocab_size"], 300) for seed in (1, 2**31 + 5)]
    assert runs[0][1] == runs[1][1] and len(runs[0][1]) > 300
    assert {k[0] for k in runs[0][0]} == {
        "prefill_step", "prefill_chunk", "decode_step"}


def test_reference_matches_the_model():
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTConfig, GPTModel, gpt_loss_fn
    from benchmark import reference

    cfg = GPTConfig(vocab_size=512, max_seq_len=64, hidden_size=64,
                    num_layers=2, num_heads=4, dtype=jnp.float32)
    model = GPTModel(cfg)
    toks = np.random.default_rng(0).integers(0, 512, (2, 33)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(toks[:, :-1]))
    leaves, tree = jax.tree.flatten(params)     # biases and scales matter
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = tree.unflatten([
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    logits = model.apply(params, jnp.asarray(toks[:, :-1]))   # (s, b, v)
    rows = reference.logits_rows(params, toks[0, :-1], np.arange(32), heads=4)
    # float32 on both sides: only the order of the sums differs
    assert float(jnp.abs(logits[:, 0] - rows).max()) < 1e-4
    want = float(gpt_loss_fn(logits, jnp.asarray(toks[:, 1:])))
    got = reference.loss(params, toks[:, :-1], toks[:, 1:], heads=4)
    assert abs(want - got) < 1e-5


def test_trace_reduce_against_hand_computed_fixture():
    from benchmark import trace_reduce
    from benchmark.readers import idle_share, module_time, op_share

    red = trace_reduce.reduce(trace_reduce.from_json(
        os.path.join(HERE, "fixtures", "trace_small.json")))
    assert red.window == (1000.0, 10000.0)
    # device 0: [2000,5000] + [6500,7500] + [8100,10000]; device 1: 4000
    assert trace_reduce.total(red.busy_intervals(0)) == 5900
    assert red.busy_s() == pytest.approx(4950e-9)
    # self time: the while holds a fusion and a copy; averaged over 2 chips
    assert red.op_seconds() == pytest.approx(
        {"while.1": 500e-9, "fusion.2": 3000e-9, "copy.3": 1450e-9})
    assert sum(red.op_seconds().values()) == pytest.approx(red.busy_s())
    assert red.module_calls("^jit_decode") == pytest.approx([3e-6, 2e-6])
    gaps = red.idle_gaps()
    assert [name for name, _ in gaps] == [
        "bench.serve.engine_step", "bench.serve.submit"]   # longest first
    assert dict(gaps) == pytest.approx({
        "bench.serve.engine_step": 1600e-9, "bench.serve.submit": 1500e-9})
    run = types.SimpleNamespace(reduced=red, facts={"pool_blocks": 1025})
    assert idle_share.read({}, run) == pytest.approx(100 * (1 - 4950 / 9000))
    assert op_share.read({"name": "copy|broadcast", "shape_has": "pool_blocks"},
                         run) == pytest.approx(100 * 2900 / 9900)
    assert module_time.read({"module": "^jit_prefill"}, run) == \
        pytest.approx(1e-3)
    empty = types.SimpleNamespace(reduced=None, facts={})
    assert idle_share.read({}, empty) is None


def test_trace_reduce_on_a_cut_of_a_chip_trace():
    """120 device operations and 20 program calls cut from the first
    traced run of gpt2-medium.train on a v5e (PR 24): the names are
    whole HLO instructions, events nest, the window is the
    ``bench.traced`` span."""
    from benchmark import trace_reduce
    from benchmark.readers import roofline

    red = trace_reduce.reduce(trace_reduce.from_json(
        os.path.join(HERE, "fixtures", "trace_train_cut.json")))
    assert red.window == (45705867.0, 3646831908.0)
    assert len(red.module_calls("^jit_step")) == 20
    assert all(0.1798 < s < 0.1801 for s in red.module_calls("^jit_step"))
    ops = red.ops[0]
    assert sum(o.self_ns for o in ops) == pytest.approx(
        trace_reduce.total(red.busy_intervals(0)))
    attention = [o for o in ops if o.name.startswith("attention")]
    assert attention and all(o.stats["opcode"] == "custom-call"
                             for o in attention)
    assert trace_reduce.label(attention[0]) == \
        "attention.21_custom-call_bf16_64_1024_64"
    whiles = [o for o in ops if o.stats.get("opcode") == "while"]
    assert whiles and whiles[0].self_ns < whiles[0].end - whiles[0].start
    run = types.SimpleNamespace(reduced=red, notes=[], peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    share = roofline.read({"name": "^attention",
                           "cost": "benchmark.costs:attention_call"}, run)
    # the forward kernel on [64, 1024, 64]: 8.6 GFLOP is 43.6 us at the
    # peak; the two calls in the cut took 353 us each
    assert share == pytest.approx(12.35, abs=0.01)
    assert "compute" in run.notes[0]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_names_units_and_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    cells = [w["name"] for w in m["workloads"]]
    for c in m["configs"]:
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert set(x.get("workloads", cells)) <= set(cells)
    assert e2e["setup_s"]["bound"] <= 0.1 and "workloads" not in e2e["setup_s"]
    for x in m["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        spec = os.path.join(ROOT, "benchmark", "layer_metrics",
                            x["name"] + ".json")
        with open(spec) as f:
            reader = json.load(f)["reader"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", reader + ".py"))
        # the metric it moves is reported wherever this one is
        moved = e2e[x["moves"]]
        assert set(x.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for cell in cells:        # every cell: set-up, another, and a layer's
        assert sum(cell in x.get("workloads", cells)
                   for x in m["end_to_end"]) >= 2
        assert any(cell in x.get("workloads", cells) for x in m["per_layer"])
