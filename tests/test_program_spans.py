"""The program's own spans (``telemetry.timeline.span``) on the
profiler's clock, and the benchmark reader that splits the device's
idle time by them.

- the serving engine's ``apex.serve.*`` spans land in a profiler trace,
  named and nested as docs/serving.md says, and cover the step;
- tokens, step reports and compiled programs do not depend on whether a
  timeline or a profiler listens;
- the ring gets its one ``prefill`` / ``prefill_chunk`` / ``decode``
  span per dispatch and none of the ``apex.serve.*`` spans, and
  ``StepTimeline.phase`` reaches the profiler;
- ``benchmark/readers/program_span.py`` on a hand-made trace, every
  answer worked out by hand.
"""

import os
import sys
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import profiler, serving, telemetry  # noqa: E402
from apex_tpu.models.gpt import GPTConfig, GPTModel  # noqa: E402
from apex_tpu.serving.kv_cache import KVCache  # noqa: E402
from apex_tpu.telemetry import timeline as _timeline  # noqa: E402

VOCAB, SEQ, HID, LAYERS, HEADS, KV = 64, 64, 32, 2, 4, 2
BLOCKS, BS, STEPS = 32, 4, 5

KINDS = ("prefill", "chunk", "decode")
PARTS = ("build", "dispatch", "wait", "fetch")
STEP_CHILDREN = ("housekeep", "admit", "finish") + KINDS
ALL_SPANS = (["apex.serve.step"]
             + [f"apex.serve.{c}" for c in STEP_CHILDREN]
             + [f"apex.serve.{k}.{p}" for k in KINDS for p in PARTS])
RING_NAME = {"prefill": "prefill", "chunk": "prefill_chunk",
             "decode": "decode"}


class CountingStep:
    """The engine's ``DecodeStep`` with its calls counted by kind."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = {k: 0 for k in KINDS}

    def prefill(self, *a, **kw):
        self.calls["prefill"] += 1
        return self.inner.prefill(*a, **kw)

    def prefill_chunk(self, *a, **kw):
        self.calls["chunk"] += 1
        return self.inner.prefill_chunk(*a, **kw)

    def decode(self, *a, **kw):
        self.calls["decode"] += 1
        return self.inner.decode(*a, **kw)


@pytest.fixture(scope="module")
def parts():
    cfg = GPTConfig(vocab_size=VOCAB, max_seq_len=SEQ, hidden_size=HID,
                    num_layers=LAYERS, num_heads=HEADS, num_kv_heads=KV,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    cache = KVCache(LAYERS, KV, HID // HEADS, num_blocks=BLOCKS,
                    block_size=BS, dtype=jnp.float32)
    return model, params, serving.make_decode_step(model, cache)


def serve(parts, *, timeline=None):
    """Five engine steps over the same three requests: two short prompts
    prefilled whole and one of 20 tokens that goes in chunks of 8."""
    model, params, inner = parts
    cache = KVCache(LAYERS, KV, HID // HEADS, num_blocks=BLOCKS,
                    block_size=BS, dtype=jnp.float32)
    step_fn = CountingStep(inner)
    engine = serving.ContinuousBatcher(
        model, params, cache, step_fn=step_fn, max_batch=4,
        min_seq_bucket=8, prefill_chunk=8,
        registry=telemetry.MetricsRegistry(), timeline=timeline)
    rng = np.random.RandomState(7)
    for i, n in enumerate((5, 20, 3)):
        engine.submit(serving.Request(
            id=i, prompt=rng.randint(0, VOCAB, n).tolist(),
            max_new_tokens=3))
    state, reports = cache.init_state(), []
    for _ in range(STEPS):
        state, report = engine.step(state)
        reports.append(report)
    tokens = {r.id: list(r.tokens) for r in engine.drain()}
    return types.SimpleNamespace(
        tokens=tokens, reports=reports, calls=step_fn.calls,
        compile_keys=inner.compile_keys())


def host_events(trace_dir, prefix=""):
    """``(name, start_ns, end_ns)`` of the host plane's events whose
    name starts with ``prefix``, by start (the longer first)."""
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def runs(parts, tmp_path_factory):
    """The same five steps five ways: nothing listening (which also
    warms the programs), the global timeline on, a private timeline,
    under the profiler, and under the profiler with a timeline."""
    plain = serve(parts)
    tl = _timeline.enable()
    try:
        with_global = serve(parts)
        ring = tl.spans()
    finally:
        _timeline.disable()
    private = telemetry.StepTimeline()
    with_private = serve(parts, timeline=private)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with profiler.trace(trace_dir):
        profiled = serve(parts)
    both_dir = str(tmp_path_factory.mktemp("trace_and_ring"))
    with profiler.trace(both_dir):
        with_both = serve(parts, timeline=telemetry.StepTimeline())
    return types.SimpleNamespace(
        plain=plain, with_global=with_global, with_private=with_private,
        profiled=profiled, with_both=with_both, ring=ring,
        private_ring=private.spans(),
        events=host_events(trace_dir, "apex."),
        events_with_ring=host_events(both_dir))


def children(events, parent):
    """The events directly inside ``parent``: inside it and inside no
    other event that is itself inside it."""
    inside = [e for e in events if e is not parent
              and parent[1] <= e[1] and e[2] <= parent[2]]
    return [e for e in inside
            if not any(o is not e and o[1] <= e[1] and e[2] <= o[2]
                       for o in inside)]


# -- (a) the spans in a profiler trace ------------------------------------

@pytest.mark.parametrize("name", ALL_SPANS)
def test_every_span_is_in_the_trace(runs, name):
    assert any(e[0] == name for e in runs.events)


@pytest.mark.parametrize("kind", KINDS)
def test_a_dispatch_span_holds_build_dispatch_wait_fetch(runs, kind):
    outer = [e for e in runs.events if e[0] == f"apex.serve.{kind}"]
    assert len(outer) == runs.profiled.calls[kind] > 0
    for span in outer:
        assert [c[0] for c in children(runs.events, span)] == [
            f"apex.serve.{kind}.{p}" for p in PARTS]


def test_every_span_lies_inside_a_step(runs):
    steps = [e for e in runs.events if e[0] == "apex.serve.step"]
    assert len(steps) == STEPS
    for e in runs.events:
        assert any(s[1] <= e[1] and e[2] <= s[2] for s in steps), e


def test_the_children_of_a_step_cover_it(runs):
    steps = [e for e in runs.events if e[0] == "apex.serve.step"]
    # By the summed time of all five steps: of the time the engine
    # spends in steps, nine tenths lies in a child span (0.915 is what
    # it reads here, where a step takes 0.1-4 ms; what lies between the
    # children is the step's own bookkeeping, 0.02-0.3 ms a step). A
    # single step's ratio is one of two short wall-clock times, and a
    # stall of the test machine between two spans decided the median
    # of five of them. The same five steps were traced twice (with and
    # without a ring): a stall only lengthens what it hits, so each
    # step's covered and uncovered time is the lesser of its two
    # readings. What lies outside a child anywhere still counts, by
    # the time it takes.
    def split(events):
        out = []
        for s in (e for e in events if e[0] == "apex.serve.step"):
            inside = sum(c[2] - c[1] for c in children(
                [e for e in events if e[0].startswith("apex.")], s))
            out.append((inside, s[2] - s[1] - inside))
        return out

    both = list(zip(split(runs.events), split(runs.events_with_ring)))
    assert len(both) == STEPS
    covered = sum(min(a[0], b[0]) for a, b in both)
    outside = sum(min(a[1], b[1]) for a, b in both)
    assert covered >= 0.9 * (covered + outside)
    for s in steps:
        names = [c[0] for c in children(runs.events, s)]
        assert names[:2] == ["apex.serve.housekeep", "apex.serve.admit"]
        assert names[-1] == "apex.serve.finish"
        assert set(names) <= {f"apex.serve.{c}" for c in STEP_CHILDREN}


@pytest.mark.parametrize("kind", KINDS)
def test_dispatch_spans_equal_the_step_functions_calls(runs, kind):
    n = sum(e[0] == f"apex.serve.{kind}.dispatch" for e in runs.events)
    assert n == runs.profiled.calls[kind]


# -- (b) nothing the engine serves depends on who listens -----------------

@pytest.mark.parametrize("how", ["with_global", "with_private", "profiled",
                                 "with_both"])
@pytest.mark.parametrize("what", ["tokens", "reports", "compile_keys",
                                  "calls"])
def test_serving_is_the_same_whoever_listens(runs, how, what):
    assert getattr(getattr(runs, how), what) == getattr(runs.plain, what)
    assert len(runs.plain.tokens) == 3
    assert all(len(t) == 3 for t in runs.plain.tokens.values())


# -- (c) the ring and the profiler see what they saw, and more ------------

@pytest.mark.parametrize("ring", ["ring", "private_ring"])
@pytest.mark.parametrize("kind", KINDS)
def test_the_ring_holds_one_span_per_dispatch(runs, ring, kind):
    spans = [s for s in getattr(runs, ring) if s.name == RING_NAME[kind]]
    assert len(spans) == runs.plain.calls[kind]
    assert all(s.category == "serving" and s.dur > 0 for s in spans)
    # ... and nothing finer: the apex.serve.* spans are the profiler's
    assert {s.name for s in getattr(runs, ring)} <= set(RING_NAME.values())


@pytest.mark.parametrize("kind", KINDS)
def test_the_rings_span_covers_the_dispatch_and_the_wait(runs, kind):
    """With a timeline on, the ring's span is in a profiler trace too:
    around the call and the wait, as ``phase()`` was before PR 26."""
    events = [e for e in runs.events_with_ring
              if e[0] == RING_NAME[kind] or e[0].startswith("apex.")]
    ring = [e for e in events if e[0] == RING_NAME[kind]]
    assert len(ring) == runs.with_both.calls[kind] > 0
    for span in ring:
        assert [c[0] for c in children(events, span)] == [
            f"apex.serve.{kind}.dispatch", f"apex.serve.{kind}.wait"]
    # ... and in none taken while no timeline listens
    assert not any(e[0] in RING_NAME.values() for e in runs.events)


def test_nothing_is_recorded_when_no_timeline_is_on(parts):
    _timeline.disable()
    serve(parts)
    assert _timeline.get_timeline().spans() == []
    assert isinstance(_timeline.span("apex.x"), jax.profiler.TraceAnnotation)
    # ring=False: the profiler's alone even when a timeline is on
    tl = telemetry.StepTimeline()
    with _timeline.span("apex.x", timeline=tl, ring=False) as sp:
        assert isinstance(sp, jax.profiler.TraceAnnotation)
    assert tl.spans() == []


@pytest.mark.parametrize("enabled", [False, True])
def test_phase_and_annotate_reach_the_profiler(tmp_path, enabled):
    tl = telemetry.StepTimeline(enabled=enabled)

    @profiler.annotate("apex.test.annotated")
    def work(x):
        return x + 1

    x = jnp.ones(4)
    with profiler.trace(str(tmp_path)):
        with tl.phase("h2d", sync_on=x):
            with tl.phase("apex.test.inner", category="checkpoint"):
                work(x)
    events = host_events(str(tmp_path))
    by_name = {e[0]: e for e in events}
    assert {"h2d", "apex.test.inner", "apex.test.annotated"} <= set(by_name)
    outer, inner = by_name["h2d"], by_name["apex.test.inner"]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert [(s.name, s.category) for s in tl.spans()] == (
        [("apex.test.inner", "checkpoint"), ("h2d", "phase")]
        if enabled else [])


class _FailingSync:
    def block_until_ready(self):
        raise KeyError("the device failed")


@pytest.mark.parametrize("fails", ["block", "sync_on"])
def test_a_failing_span_is_closed_and_recorded(tmp_path, fails):
    tl = telemetry.StepTimeline()
    with profiler.trace(str(tmp_path)):
        with pytest.raises(KeyError):
            with _timeline.span(
                    "apex.test.failing", category="c", timeline=tl,
                    sync_on=_FailingSync() if fails == "sync_on" else None):
                if fails == "block":
                    raise KeyError("the block failed")
        with _timeline.span("apex.test.after", timeline=tl):
            pass
    assert [(s.name, s.category) for s in tl.spans()] == [
        ("apex.test.failing", "c"), ("apex.test.after", "phase")]
    # the annotation was closed: the event is in the trace, and the
    # next span does not lie inside it
    failing, after = host_events(str(tmp_path), "apex.test.")
    assert failing[0] == "apex.test.failing" and failing[2] <= after[1]


# -- (d) the reader, on a trace made by hand ------------------------------

def hand_trace(tmp_path, host):
    """A device that runs three programs of one operation each, in
    [100, 200], [300, 400] and [800, 1000] of a window [0, 1000] (ns),
    and the given host events."""
    import json

    doc = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 100, 100],
                ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 300, 100],
                ["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 800, 200]]},
            {"name": "XLA Modules", "events": [
                ["jit_decode_fn(1)", 100, 100], ["jit_decode_fn(1)", 300, 100],
                ["jit_decode_fn(1)", 800, 200]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": (
            [["bench.traced", 0, 1000]] + host)}]}]}
    path = os.path.join(str(tmp_path), "trace.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


# one idle gap, [400, 800], crosses five spans: wait ends at 420, fetch
# runs to 450, the decode span's own time to 470, finish to 500, the
# step ends at 510; the benchmark has [510, 600] to itself; the next
# step opens with housekeep [600, 640] and admit [640, 650], its own
# time to 660, then build [670, 700] inside decode [660, 1000] and
# dispatch from 700, in which the device starts at 800. The first two
# gaps, [0, 100] and [200, 300], lie in the first step's dispatch and
# wait.
HOST = [
    ["apex.serve.step", 10, 500], ["apex.serve.decode", 40, 430],
    ["apex.serve.decode.dispatch", 50, 200],
    ["apex.serve.decode.wait", 250, 170],
    ["apex.serve.decode.fetch", 420, 30],
    ["apex.serve.finish", 470, 30],
    ["apex.serve.step", 600, 400], ["apex.serve.housekeep", 600, 40],
    ["apex.serve.admit", 640, 10], ["apex.serve.decode", 660, 340],
    ["apex.serve.decode.build", 670, 30],
    ["apex.serve.decode.dispatch", 700, 300],
]
BY_SPAN = {                       # ns of idle, by innermost span
    "(outside)": 10 + 90,                         # [0,10] and [510,600]
    "apex.serve.step": 30 + 10 + 10,       # [10,40] [500,510] [650,660]
    "apex.serve.decode": 10 + 20 + 10,     # [40,50] [450,470] [660,670]
    "apex.serve.decode.dispatch": 50 + 50 + 100,
    "apex.serve.decode.wait": 50 + 20,            # [250,300] [400,420]
    "apex.serve.decode.fetch": 30, "apex.serve.finish": 30,
    "apex.serve.housekeep": 40, "apex.serve.admit": 10,
    "apex.serve.decode.build": 30,
}
METRICS = {                       # per step, of two traced steps
    "host_gap_schedule_ms": (50 + 40 + 30 + 40 + 10) * 1e-6 / 2,
    "host_gap_build_ms": 30e-6 / 2,
    "host_gap_dispatch_ms": 200e-6 / 2,
    "host_gap_sync_ms": (70 + 30) * 1e-6 / 2,
    "engine_dispatches_per_step": 1.0,
}


@pytest.fixture
def hand_run(tmp_path, monkeypatch):
    from benchmark import trace_reduce

    def make(host, steps=2):
        profile = trace_reduce.from_json(hand_trace(tmp_path, host))
        monkeypatch.setattr(trace_reduce, "load", lambda trace_dir: profile)
        return types.SimpleNamespace(
            reduced=trace_reduce.reduce(profile), trace_dir="unused",
            counters={"traced_steps": steps} if steps else {},
            end_to_end={}, window_s=0.0, notes=[])

    return make


def metric_spec(name, reader="program_span"):
    import json

    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == reader
    return spec["params"]


@pytest.mark.parametrize("gap,want", [
    ((400.0, 510.0), {          # five spans, to the end of the step
        "apex.serve.decode.wait": 20, "apex.serve.decode.fetch": 30,
        "apex.serve.decode": 20, "apex.serve.finish": 30,
        "apex.serve.step": 10}),
    ((400.0, 800.0), {          # the whole gap: ten, and the benchmark
        "apex.serve.decode.wait": 20, "apex.serve.decode.fetch": 30,
        "apex.serve.decode": 20 + 10, "apex.serve.finish": 30,
        "apex.serve.step": 10 + 10, "(outside)": 90,
        "apex.serve.housekeep": 40, "apex.serve.admit": 10,
        "apex.serve.decode.build": 30, "apex.serve.decode.dispatch": 100}),
    ((520.0, 590.0), {"(outside)": 70}),
])
def test_one_gap_is_cut_at_the_edges_of_the_spans_it_crosses(gap, want):
    from benchmark.readers import program_span

    spans = sorted(((n, float(a), float(a + d)) for n, a, d in HOST),
                   key=lambda s: s[1])
    got = program_span.idle_by_span([gap], spans)
    assert {k: v for k, v in got.items() if v} == want
    assert sum(got.values()) == gap[1] - gap[0]


@pytest.mark.parametrize("name", sorted(BY_SPAN))
def test_idle_time_by_innermost_span(hand_run, name):
    from benchmark.readers import program_span

    found = program_span.split_of(hand_run(HOST))
    assert found["idle_ms"][name] == pytest.approx(BY_SPAN[name] * 1e-6 / 2)
    assert sum(found["idle_ms"].values()) == pytest.approx(600e-6 / 2)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_each_metric_on_the_hand_made_trace(hand_run, name):
    from benchmark.readers import program_span

    got = program_span.read(metric_spec(name), hand_run(HOST))
    assert got == pytest.approx(METRICS[name])


def test_the_gap_metrics_and_the_outside_share_are_host_gap_ms(hand_run):
    from benchmark.readers import formula, program_span

    run = hand_run(HOST)
    parts = [program_span.read(metric_spec(m), run)
             for m in sorted(METRICS) if m.startswith("host_gap_")]
    outside = program_span.split_of(run)["idle_ms"]["(outside)"]
    assert outside == pytest.approx(100e-6 / 2)
    assert sum(parts) + outside == pytest.approx(
        formula.read(metric_spec("host_gap_ms", "formula"), run))
    # one parse and one note a run, whatever the number of metrics
    assert len(run.notes) == 1 and "apex.serve.decode.wait" in run.notes[0]
    assert "the benchmark's own share" in run.notes[0]


def launches(*starts):
    return [["tpu::System::Execute", t, 5] for t in starts]


@pytest.mark.parametrize("starts,early", [
    ((60, 270, 720), 0.0),      # every program starts after its launch
    ((130, 290, 810), 30.0),    # the first is stamped 30 ns before its own
    ((130, 290), None),         # not a launch for each program
    ((), None),                 # another runtime: none at all
])
def test_how_early_the_trace_stamps_the_device(hand_run, starts, early):
    from benchmark.readers import program_span

    run = hand_run(HOST + launches(*starts))
    assert program_span.split_of(run)["early_ns"] == early
    assert ("as the trace stamps it" in run.notes[0]) == (early is None)
    if not early:               # nothing moved: the answers above
        for name, want in METRICS.items():
            assert program_span.read(metric_spec(name), run) == (
                pytest.approx(want))


def test_the_device_is_moved_to_where_no_program_precedes_its_launch(
        hand_run):
    """Moved 30 ns later the device is idle in [0, 130], [230, 330] and
    [430, 830] (its last program is cut at the window's end): the
    first dispatch holds [50, 130] and [230, 250], the second
    [700, 830]; wait holds [250, 330] and nothing after 420, fetch
    [430, 450]."""
    from benchmark.readers import program_span

    run = hand_run(HOST + launches(130, 290, 810))
    idle = program_span.split_of(run)["idle_ms"]
    assert idle["apex.serve.decode.dispatch"] == pytest.approx(
        (80 + 20 + 130) * 1e-6 / 2)
    assert idle["apex.serve.decode.wait"] == pytest.approx(80e-6 / 2)
    assert idle["apex.serve.decode.fetch"] == pytest.approx(20e-6 / 2)
    moved = {"apex.serve.decode.dispatch", "apex.serve.decode.wait",
             "apex.serve.decode.fetch"}
    for name in set(BY_SPAN) - moved:
        assert idle[name] == pytest.approx(BY_SPAN[name] * 1e-6 / 2)
    assert "timeline moved 0.000 ms later" in run.notes[0]
    assert program_span.read(metric_spec("host_gap_dispatch_ms"), run) == (
        pytest.approx(230e-6 / 2))


@pytest.mark.parametrize("case", ["no_apex_span", "no_traced_steps",
                                  "no_device_plane", "not_traced"])
def test_the_reader_returns_nothing(hand_run, case):
    from benchmark.readers import program_span

    run = hand_run([["bench.serve.engine_step", 10, 500]]
                   if case == "no_apex_span" else HOST,
                   steps=0 if case == "no_traced_steps" else 2)
    if case == "no_device_plane":
        run.reduced.ops.clear()
    if case == "not_traced":
        run.reduced = None
    for name in METRICS:
        assert program_span.read(metric_spec(name), run) is None
    assert run.notes == []
