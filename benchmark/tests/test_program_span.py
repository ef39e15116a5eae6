"""``readers/program_span.py`` against a cut of a chip trace, the numbers
read off the cut by hand.

``fixtures/trace_serve_spans.json`` is the first four engine steps of
the first traced run of cerebras-gpt-1.3b.serve-chat that held the
program's own spans (one v5e, PR 26), made by ``cut_trace.py``: four
calls of ``jit_decode_fn``, the 221 outermost device operations, the
host's ``bench.*`` and ``apex.*`` events and the runtime's four
launches. Each step is one decode dispatch; the device is idle for
about 5 ms between two programs. It is one of the traces that stamp the
device a millisecond early: every program "starts" before the host
launches it.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "fixtures", "trace_serve_spans.json")
STEPS = 4

# The second gap of the cut: the device's last operation of step 2 ends
# at 118,697,062 ns and its first of step 3 starts at 123,571,936. The
# host's events in between, from the fixture (start, end):
#   apex.serve.decode.wait   ... 120,738,785       (step 2's)
#   apex.serve.decode.fetch  120,746,095 121,774,525
#   apex.serve.decode        ... 121,776,515
#   apex.serve.finish        121,833,475 121,884,415
#   apex.serve.step          ... 121,887,465
#   apex.serve.step          121,933,475 ...       (step 3's)
#   apex.serve.housekeep     121,940,715 121,971,385
#   apex.serve.admit         121,973,015 121,977,595
#   apex.serve.decode        122,002,335 ...
#   apex.serve.decode.build  122,003,315 122,090,635
#   apex.serve.decode.dispatch 122,093,095 ...
GAP = (118697062.0, 123571936.0)
GAP_BY_SPAN = {
    "apex.serve.decode.wait": 120738785 - 118697062,
    "apex.serve.decode.fetch": 121774525 - 120746095,
    # the dispatch span's own time: wait -> fetch, fetch -> its end,
    # its start -> build, build -> dispatch
    "apex.serve.decode": ((120746095 - 120738785) + (121776515 - 121774525)
                          + (122003315 - 122002335)
                          + (122093095 - 122090635)),
    "apex.serve.finish": 121884415 - 121833475,
    # the step's own: decode -> finish, finish -> its end, its start ->
    # housekeep, housekeep -> admit, admit -> decode
    "apex.serve.step": ((121833475 - 121776515) + (121887465 - 121884415)
                        + (121940715 - 121933475)
                        + (121973015 - 121971385)
                        + (122002335 - 121977595)),
    "(outside)": 121933475 - 121887465,        # the benchmark's own
    "apex.serve.housekeep": 121971385 - 121940715,
    "apex.serve.admit": 121977595 - 121973015,
    "apex.serve.decode.build": 122090635 - 122003315,
    "apex.serve.decode.dispatch": 123571936 - 122093095,
}

# Over the whole cut. The window (bench.traced) is 154,985,630 ns, the
# union of the operations 135,193,102: 19,792,528 idle, 4.948132 ms a
# step, which is host_gap_ms. Every fetch, build, housekeep, admit and
# finish span lies inside an idle stretch, so its idle time is its
# duration; a dispatch span's is its start to the device's first
# operation after it, a wait span's the device's last operation to its
# end; the 166 gaps between a program's operations add 1,861 ns, inside
# dispatch and wait.
FETCH = 1122900 + 1028430 + 1089980 + 1161150
BUILD = 81480 + 96000 + 87320 + 101240
DISPATCH = ((46034234 - 44799975) + (84899163 - 83499030)
            + (123571936 - 122093095) + (162420874 - 160854820))
WAIT = ((81997140 - 79834086) + (120738785 - 118697062)
        + (159382190 - 157368784) + (198221465 - 196221238))
OUTSIDE = ((44638475 - 44565995) + (83331640 - 83263470)
           + (121933475 - 121887465) + (160686030 - 160638950)
           + (199551625 - 199517995))
IDLE = 154985630 - 135193102
PER_STEP_MS = 1e-6 / STEPS
# The runtime's launches (tpu::System::Execute) start at 47,076,220,
# 85,861,844, 124,591,779 and 163,435,404; the four programs (XLA
# Modules) at 46,033,944, 84,898,864, 123,571,639 and 162,420,583: each
# a millisecond *before* its launch. The reader moves the device's
# timeline later by the largest of the four. Every program's first
# operation then still lies inside its dispatch span (the first: at
# 47,076,510 of a span that ends at 47,218,034) and its last before its
# wait span's end, so the move takes EARLY a step from wait to dispatch
# and leaves the rest.
EARLY = max(47076220 - 46033944, 85861844 - 84898864,
            124591779 - 123571639, 163435404 - 162420583)
SCHEDULE = IDLE - 1861 - WAIT - FETCH - DISPATCH - BUILD - OUTSIDE


def metrics(early):
    """value, and how far the 1,861 ns may move it"""
    return {
        "host_gap_build_ms": (BUILD * PER_STEP_MS, 1e-9),
        "host_gap_dispatch_ms": ((DISPATCH + STEPS * early) * PER_STEP_MS,
                                 1861 * PER_STEP_MS),
        "host_gap_sync_ms": ((WAIT - STEPS * early + FETCH) * PER_STEP_MS,
                             1861 * PER_STEP_MS),
        "host_gap_schedule_ms": (SCHEDULE * PER_STEP_MS, 1861 * PER_STEP_MS),
        "engine_dispatches_per_step": (1.0, 1e-9),
    }


METRICS = metrics(EARLY)


def spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def a_run(monkeypatch, launches=True):
    from benchmark import trace_reduce
    from benchmark.readers import program_span

    profile = trace_reduce.from_json(FIXTURE)
    if not launches:
        for plane in profile.planes:
            for line in plane.lines:
                line.events = [e for e in line.events
                               if e.name != program_span.LAUNCH]
    monkeypatch.setattr(trace_reduce, "load", lambda trace_dir: profile)
    return types.SimpleNamespace(
        reduced=trace_reduce.reduce(profile), trace_dir="unused",
        counters={"traced_steps": STEPS}, end_to_end={}, window_s=0.0,
        notes=[])


@pytest.fixture
def run(monkeypatch):
    return a_run(monkeypatch)


def test_the_cut_is_four_decode_steps(run):
    red = run.reduced
    assert red.window == (44565995.0, 199551625.0)
    assert len(red.module_calls("^jit_decode_fn")) == STEPS
    assert len(red.ops[0]) == 221
    assert red.busy_s() == pytest.approx(135193102e-9)
    # the midpoint rule gives every gap to the benchmark's one span
    assert red.idle_gaps() == [
        ("bench.serve.engine_step", pytest.approx(IDLE * 1e-9))]


def test_one_gap_of_the_cut_by_hand(run):
    from benchmark import trace_reduce
    from benchmark.readers import program_span

    spans = program_span.program_spans(
        trace_reduce.from_json(FIXTURE), run.reduced.window)
    assert len(spans) == 9 * STEPS
    got = program_span.idle_by_span([GAP], spans)
    assert {k: v for k, v in got.items() if v} == GAP_BY_SPAN
    assert sum(GAP_BY_SPAN.values()) == GAP[1] - GAP[0] == 4874874


def test_the_devices_timeline_is_moved_to_where_no_program_precedes_its_launch(
        run):
    from benchmark import trace_reduce
    from benchmark.readers import program_span

    red, profile = run.reduced, trace_reduce.from_json(FIXTURE)
    assert EARLY == 1042276
    assert program_span.stamped_early(profile, red) == EARLY
    first = program_span.idle_intervals(red, EARLY)[0]
    assert first == (red.window[0], 46034234.0 + EARLY)     # first operation
    assert (sum(b - a for a, b in program_span.idle_intervals(red, EARLY))
            == sum(b - a for a, b in program_span.idle_intervals(red))
            == IDLE)
    split = program_span.split_of(run)
    assert split["early_ns"] == EARLY
    assert "timeline moved 1.042 ms later" in run.notes[0]


@pytest.mark.parametrize("name", sorted(METRICS))
@pytest.mark.parametrize("launches", [True, False])
def test_each_metric_on_the_cut(monkeypatch, name, launches):
    """With the launches the device's timeline is pinned to them; a
    trace that holds none is read as it is stamped, and says so."""
    from benchmark.readers import program_span

    run = a_run(monkeypatch, launches)
    want, slack = metrics(EARLY if launches else 0)[name]
    got = program_span.read(spec(name)["params"], run)
    assert spec(name)["reader"] == "program_span"
    assert abs(got - want) <= slack + 1e-12
    assert got == pytest.approx(want, rel=1e-3)
    assert ("as the trace stamps it" in run.notes[0]) == (not launches)


def test_the_parts_add_up_to_host_gap_ms(run):
    from benchmark.readers import formula, program_span

    gaps = [program_span.read(spec(m)["params"], run)
            for m in METRICS if m.startswith("host_gap_")]
    outside = program_span.split_of(run)["idle_ms"]["(outside)"]
    assert outside == pytest.approx(OUTSIDE * PER_STEP_MS)
    whole = formula.read(spec("host_gap_ms")["params"], run)
    assert whole == pytest.approx(IDLE * PER_STEP_MS)
    assert sum(gaps) + outside == pytest.approx(whole)
    # 98.6% of the idle time lies inside the program's spans
    assert 1 - outside / whole > 0.98
    assert len(run.notes) == 1          # one parse, one note
    # ... which gives the sum that no alignment of the clocks moves
    launch = sum(program_span.read(spec(m)["params"], run)
                 for m in ("host_gap_dispatch_ms", "host_gap_sync_ms"))
    assert f"idle in dispatch + wait + fetch {launch:.3f} ms" in run.notes[0]


def test_a_trace_without_program_spans_reads_as_nothing(monkeypatch):
    """The parent of PR 26 has no ``apex.`` span: the cut of its train
    trace holds ``bench.`` spans only."""
    from benchmark import trace_reduce
    from benchmark.readers import program_span

    profile = trace_reduce.from_json(
        os.path.join(HERE, "fixtures", "trace_train_cut.json"))
    monkeypatch.setattr(trace_reduce, "load", lambda trace_dir: profile)
    run = types.SimpleNamespace(
        reduced=trace_reduce.reduce(profile), trace_dir="unused",
        counters={"traced_steps": 20}, notes=[])
    for name in METRICS:
        assert program_span.read(spec(name)["params"], run) is None
    assert run.notes == []
