"""``readers/scope_time.py`` against a cut of a chip trace and the
scope tables of the programs that ran in it.

``fixtures/trace_scope_time.json`` is three program calls of PR 37's
first traced run of cerebras-gpt-1.3b.serve-code (one v5e, seed
2718281828), with the 3,300 device operations inside them: the second
call of the decode program (8 lanes, table width 128: 14.810 ms) and of
two chunk programs that share the name ``jit_prefill_chunk_fn`` and
differ in the table's width alone (one lane of 256 rows at width 64:
8.052 ms; at width 128: 10.571 ms). Operation names are cut to 160
characters (name, result and opcode survive; a ``while``'s tuple does
not). Written by ``trace_reduce.to_json``. ``fixtures/scope_tables.json``
is a ``json.dump`` of ``compiled.scope_tables()`` of the same process
for those three programs and for the decode program of the other width
(which did not run in the cut), less what the reader does not read.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import copy
import json
import os
import types

import pytest

from benchmark import trace_reduce
from benchmark.readers import scope_time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DECODE, CHUNK = "^jit_decode_fn", "^jit_prefill"
# the program calls of the cut: (name, signature, ms)
CALLS = {
    "decode": ("jit_decode_fn(8068208691777047520)",
               {"batch": 8, "table_width": 128}, 14.810158),
    "chunk-64": ("jit_prefill_chunk_fn(8016753326845211451)",
                 {"batch": 1, "seq": 256, "table_width": 64}, 8.052370),
    "chunk-128": ("jit_prefill_chunk_fn(3567250767682965693)",
                  {"batch": 1, "seq": 256, "table_width": 128}, 10.570517),
}
PARTS = ("embed", "attention", "cache", "mlp", "head", "unscoped")


def tables():
    with open(os.path.join(HERE, "fixtures", "scope_tables.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.from_json(
        os.path.join(HERE, "fixtures", "trace_scope_time.json")))


@pytest.fixture
def a_run(reduced, monkeypatch):
    """A traced run whose process hands out the given scope tables."""
    from apex_tpu.telemetry import compiled

    def make(scope_tables):
        monkeypatch.setattr(compiled, "scope_tables", lambda: scope_tables)
        return types.SimpleNamespace(
            reduced=reduced, notes=[], compiles=types.SimpleNamespace(n=0))

    return make


def read(run, module, part):
    return scope_time.read(
        {"module": module, "part": part, "of": "ms_per_call"}, run)


@pytest.mark.parametrize("module,calls", [(DECODE, ["decode"]),
                                          (CHUNK, ["chunk-64", "chunk-128"])])
def test_the_parts_add_up_to_the_calls_device_time(reduced, a_run, module,
                                                   calls):
    """The parts and ``unscoped`` of a kind of program add up to what
    ``module_calls`` reads for it, a call."""
    run = a_run(tables())
    total = sum(read(run, module, part) or 0.0 for part in PARTS)
    want = sum(CALLS[c][2] for c in calls) / len(calls)
    assert 1e3 * sum(reduced.module_calls(module)) / len(calls) \
        == pytest.approx(want, rel=1e-6)
    assert total == pytest.approx(want, rel=0.01)
    # and several parts joined by "+" are their sum
    assert read(run, module, "attention+mlp+embed") == pytest.approx(
        sum(read(run, module, p) for p in ("attention", "mlp", "embed")))


@pytest.mark.parametrize("part,ms", [
    # read off the cut by the join itself, then held to the hand-made
    # table of PERF.md section 5 (serve-code, PR 29 to PR 34): kv_gather
    # 4.60 + zero_context 0.19 and the slots' updates; the attention
    # kernel 5.35 and the projections
    ("cache", (4.9, 5.6)), ("attention", (6.5, 7.0)), ("mlp", (2.1, 2.5)),
    ("head", (0.25, 0.35)), ("unscoped", (0.0, 0.1)), ("embed", (0.0, 0.01)),
])
def test_a_decode_call_by_part(a_run, part, ms):
    value = read(a_run(tables()), DECODE, part)
    assert ms[0] <= value <= ms[1]


@pytest.mark.parametrize("call", list(CALLS))
def test_buckets_under_one_name_are_told_apart(reduced, call):
    """Each executed program goes to the registered program of its name
    whose instructions ran inside it: the table of its own bucket, for
    both chunk programs (whose instruction names mostly coincide) and
    for the decode program of the two registered."""
    name, signature, _ = CALLS[call]
    first = min(reduced.ops)
    module = next(m for m in reduced.modules[first] if m.name == name)
    inside = [o for o in reduced.ops[first]
              if o.start >= module.start and o.end <= module.end]
    same_name = [t for t in tables()
                 if t["name"] == name.partition("(")[0]]
    assert len(same_name) >= 2
    chosen = scope_time.program_of(inside, same_name)
    assert {k: chosen["signature"][k] for k in signature} == signature


def test_the_note_holds_the_whole_split(a_run):
    run = a_run(tables())
    assert read(run, DECODE, "cache") is not None
    assert read(run, CHUNK, "head") is not None
    (note,) = run.notes                      # joined once a run
    assert "jit_decode_fn 1 calls of 1 program(s), 14.810 ms a call" in note
    assert "jit_prefill_chunk_fn 2 calls of 2 program(s)" in note
    assert "in fusions that hold two parts or more" in note
    assert "NOT REPORTED" not in note


def test_a_text_without_scopes_gives_the_note_and_no_metric(a_run):
    """An executable from an older tree's cache entry: its text holds
    no part, the source opens five. The decode program's metrics are
    not reported (no zeros), the note names the parts; the chunk
    programs, whose texts are whole, are read as before."""
    stale = copy.deepcopy(tables())
    for table in stale:
        if table["name"] == "jit_decode_fn":
            table["parts"] = {}
            table["missing_parts"] = ["attention", "cache", "embed", "head",
                                      "mlp"]
    run = a_run(stale)
    assert all(read(run, DECODE, part) is None for part in PARTS)
    assert read(run, CHUNK, "head") > 0
    (note,) = run.notes
    assert ("NOT REPORTED: the compiled text lacks attention, cache, embed, "
            "head, mlp") in note


@pytest.mark.parametrize("why", ["no trace", "no device plane", "no tables",
                                 "an older tree", "no such program"])
def test_nothing_to_read_returns_nothing(a_run, monkeypatch, why):
    run = a_run(tables())
    module = DECODE
    if why == "no trace":
        run.reduced = None
    elif why == "no device plane":
        run.reduced = trace_reduce.Reduced((0.0, 1.0), {}, {}, [])
    elif why == "no tables":
        run = a_run([])
    elif why == "an older tree":            # no scope_tables to call
        import apex_tpu.telemetry.compiled as compiled

        monkeypatch.delattr(compiled, "scope_tables")
    else:
        module = "^jit_step"
    assert read(run, module, "cache") is None
    assert all("NOT REPORTED" not in n for n in run.notes)


def test_the_thirteen_metrics_are_this_readers():
    """Every ``*_call_ms.<part>`` metric of ``BENCHMARK.json`` names this
    reader, a program and parts of the program's own list."""
    from apex_tpu.telemetry import compiled

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]
             if m["name"].startswith(("decode_call_ms.", "chunk_call_ms.",
                                      "train_call_ms."))]
    assert len(names) == 13 and names == [
        m["name"] for m in manifest["per_layer"][-13:]]
    for name in names:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "scope_time"
        assert spec["params"]["of"] == "ms_per_call"
        assert spec["params"]["module"] in (DECODE, CHUNK, "^jit_step")
        assert set(spec["params"]["part"].split("+")) <= {
            *compiled.PARTS, compiled.UNSCOPED}
