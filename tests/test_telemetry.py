"""Telemetry subsystem: registry, sinks, timeline, cost, and the
instrumentation pass across the runtime (docs/observability.md)."""

import io
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import telemetry
from apex_tpu.telemetry import cost as tcost
from apex_tpu.telemetry import metrics as tmetrics
from apex_tpu.telemetry import timeline as ttimeline


@pytest.fixture(autouse=True)
def fresh_telemetry():
    """Every test sees a clean registry + disabled global timeline."""
    telemetry.reset()
    yield
    telemetry.reset()


def small_step(rng, scaler=None, **kw):
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.train_step import make_train_step

    params = {"w": jnp.asarray(rng.randn(192).astype(np.float32)),
              "b": jnp.asarray(rng.randn(16).astype(np.float32))}
    opt = FusedAdam(lr=1e-3)
    state = opt.init(params)
    g = jnp.asarray(rng.randn(state.space.total).astype(np.float32) * 1e-3)
    return make_train_step(opt, scaler=scaler, **kw), state, g


class TestRegistry:
    def test_counter_gauge_histogram_and_labels(self):
        reg = telemetry.registry()
        c = reg.counter("c", "help")
        c.inc()
        c.inc(2.0, action="rollback")
        assert c.value() == 1.0
        assert c.value(action="rollback") == 2.0
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("g")
        g.set(3.0)
        g.inc()
        g.dec(0.5)
        assert g.value() == 3.5
        h = reg.histogram("h", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(50.0)
        snap = reg.snapshot()
        hs = snap["histograms"]["h"]
        # cumulative prometheus-style buckets + implicit +Inf
        assert hs["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 3}
        assert hs["count"] == 3
        assert hs["sum"] == pytest.approx(50.55)
        assert snap["counters"]['c{action="rollback"}'] == 2.0
        json.dumps(snap)                       # one JSON-able dict

    def test_get_or_create_and_kind_mismatch(self):
        reg = telemetry.registry()
        assert reg.counter("m") is reg.counter("m")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("m")

    def test_histogram_timer(self):
        reg = telemetry.registry()
        h = reg.histogram("t")
        with h.time(op="x"):
            pass
        snap = h.series()['t{op="x"}']
        assert snap["count"] == 1 and snap["sum"] >= 0.0

    def test_info_blobs(self):
        reg = telemetry.registry()
        reg.set_info("backend_report", {"backend": "tpu"})
        assert reg.get_info("backend_report") == {"backend": "tpu"}
        assert reg.snapshot()["info"]["backend_report"]["backend"] == "tpu"
        with pytest.raises(TypeError):
            reg.set_info("bad", object())      # must be JSON-able

    def test_events_count_and_route_to_sinks(self):
        reg = telemetry.registry()
        sink = telemetry.InMemorySink()
        reg.add_sink(sink)
        reg.event("probe", ok=True)
        reg.event("probe", ok=False)
        assert reg.counter("telemetry_events").value(event="probe") == 2.0
        assert [e["ok"] for e in sink.events] == [True, False]
        assert all(e["event"] == "probe" for e in sink.events)

    def test_broken_sink_never_breaks_publisher(self):
        class Dead:
            def write_event(self, e):
                raise RuntimeError("disk on fire")

            def write_snapshot(self, s):
                raise RuntimeError("still on fire")

        reg = telemetry.registry()
        reg.add_sink(Dead())
        reg.event("x")                          # must not raise
        reg.flush()

    def test_thread_safety_smoke(self):
        reg = telemetry.registry()
        c = reg.counter("racy")

        def worker():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value() == 8000.0

    def test_reset_clears_everything(self):
        reg = telemetry.registry()
        reg.counter("c").inc()
        reg.set_info("i", 1)
        reg.add_sink(telemetry.InMemorySink())
        reg.reset()
        snap = reg.snapshot()
        assert snap["counters"] == {} and "info" not in snap
        assert reg.sinks == []


class TestSinks:
    def test_stdout_sink_line_protocol(self):
        buf = io.StringIO()
        sink = telemetry.StdoutSink(stream=buf)
        reg = telemetry.registry()
        reg.add_sink(sink)
        reg.event("hello", n=1)
        reg.flush()
        lines = buf.getvalue().strip().splitlines()
        assert len(lines) == 2
        for line in lines:
            assert line.startswith("telemetry ")
            json.loads(line[len("telemetry "):])

    def test_jsonl_sink_writes_valid_lines(self, tmp_path):
        sink = telemetry.JsonlSink(str(tmp_path), name="tele")
        sink.write_event({"event": "a", "n": 1})
        sink.write_snapshot({"counters": {}})
        sink.close()
        assert sink.path and os.path.basename(sink.path).startswith("tele_")
        with open(sink.path) as f:
            lines = [json.loads(line) for line in f]
        assert lines[0]["type"] == "event" and lines[0]["event"] == "a"
        assert lines[1]["type"] == "snapshot"

    def test_jsonl_sink_claim_is_o_excl(self, tmp_path, monkeypatch):
        """A pre-existing file with the exact claim name (the TOCTOU
        partner) is never clobbered: O_CREAT|O_EXCL falls through to a
        monotonic-disambiguated name — the records.py PR-3 protocol."""
        monkeypatch.setattr(tmetrics.time, "strftime",
                            lambda *a: "20260101T000000Z")
        victim = tmp_path / "tele_20260101T000000Z.jsonl"
        victim.write_text('{"keep": "me"}\n')
        sink = telemetry.JsonlSink(str(tmp_path), name="tele")
        sink.write_event({"event": "x"})
        sink.close()
        assert sink.path != str(victim)
        assert json.loads(victim.read_text())["keep"] == "me"
        # the disambiguator is monotonic-ns: strictly increasing names
        sink2 = telemetry.JsonlSink(str(tmp_path), name="tele")
        sink2.write_event({"event": "y"})
        sink2.close()
        assert sink2.path != sink.path

    def test_jsonl_sink_fsync_fault_leaves_no_ghost(self, tmp_path):
        """The directory fsync after the claim is part of the claim: a
        fault there unlinks the claimed file (no truncated ghost), and
        the registry's event() absorbs the sink failure."""
        from apex_tpu.resilience import faults

        sink = telemetry.JsonlSink(str(tmp_path), name="tele")
        with faults.inject(io_errors={"record_fsync": frozenset({0})}):
            with pytest.raises(OSError):
                sink.write_event({"event": "x"})
        assert list(tmp_path.iterdir()) == []   # claim unlinked
        # registry-routed events degrade instead of raising
        reg = telemetry.registry()
        reg.add_sink(sink)
        with faults.inject(io_errors={"record_fsync": frozenset({0})}):
            reg.event("still_ok")
        # and a later write claims cleanly
        sink.write_event({"event": "y"})
        sink.close()
        with open(sink.path) as f:
            assert json.loads(f.readline())["event"] == "y"

    def test_jsonl_sink_defaults_to_records_dir(self, tmp_path,
                                                monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        sink = telemetry.JsonlSink()
        sink.write_event({"event": "x"})
        sink.close()
        assert os.path.dirname(sink.path) == str(tmp_path)


class TestStepTimeline:
    def test_phases_steps_and_summary(self):
        tl = telemetry.StepTimeline(capacity=64)
        for _ in range(3):
            with tl.step_scope():
                with tl.phase("data_wait"):
                    pass
                with tl.phase("step"):
                    pass
        summ = tl.summary()
        assert summ["steps"] == 3 and summ["dropped_spans"] == 0
        # 3 phases x 3 steps (host_step span per step scope)
        assert summ["phases"]["data_wait"]["count"] == 3
        assert summ["phases"]["step"]["count"] == 3
        assert summ["phases"]["host_step"]["count"] == 3
        assert summ["phases"]["step"]["mean_ms"] >= 0.0
        # spans carry their step index
        assert {s.step for s in tl.spans() if s.name == "step"} == {0, 1, 2}

    def test_ring_buffer_bounds_memory(self):
        tl = telemetry.StepTimeline(capacity=4)
        for i in range(10):
            tl.record_span(f"s{i}", float(i), 0.001)
        assert len(tl.spans()) == 4
        assert tl.summary()["dropped_spans"] == 6
        assert [s.name for s in tl.spans()] == ["s6", "s7", "s8", "s9"]

    def test_disabled_timeline_records_nothing(self):
        tl = telemetry.StepTimeline(enabled=False)
        with tl.step_scope():
            with tl.phase("step"):
                pass
        tl.record_span("x", 0.0, 1.0)
        assert tl.spans() == []
        assert tl.summary()["phases"] == {}

    def test_export_trace_is_valid_chrome_trace(self, tmp_path):
        tl = telemetry.StepTimeline()
        with tl.step_scope():
            with tl.phase("h2d"):
                pass
            with tl.phase("step", category="train_step"):
                pass
        path = str(tmp_path / "trace.json")
        tl.export_trace(path)
        with open(path) as f:
            trace = json.load(f)         # loads as valid JSON
        events = trace["traceEvents"]
        assert isinstance(events, list) and events
        complete = [e for e in events if e["ph"] == "X"]
        assert {e["name"] for e in complete} == {"h2d", "step",
                                                 "host_step"}
        for e in complete:
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert e["pid"] == os.getpid()
            assert "step" in e["args"]
        # category -> tid metadata rows for readable perfetto tracks
        meta = [e for e in events if e["ph"] == "M"]
        assert {m["args"]["name"] for m in meta} >= {"phase",
                                                     "train_step"}

    def test_phase_sync_on_blocks_on_device_value(self):
        tl = telemetry.StepTimeline()
        x = jnp.ones((64,))
        with tl.phase("step", sync_on=x):
            y = x * 2.0
        del y
        assert tl.summary()["phases"]["step"]["count"] == 1

    def test_wrap_iter_times_data_wait(self):
        tl = telemetry.StepTimeline()
        out = list(tl.wrap_iter([1, 2, 3]))
        assert out == [1, 2, 3]
        assert tl.summary()["phases"]["data_wait"]["count"] == 3

    def test_publish_pushes_phase_gauges(self):
        tl = telemetry.StepTimeline()
        with tl.phase("h2d"):
            pass
        tl.publish()
        g = telemetry.registry().gauge("timeline_phase_ms")
        assert g.value(phase="h2d") >= 0.0

    def test_global_timeline_env_and_enable(self, monkeypatch):
        assert not ttimeline.global_enabled()
        tl = ttimeline.enable(capacity=16)
        assert ttimeline.global_enabled()
        ttimeline.record_global_span("x", 0.0, 0.5)
        assert tl.spans()[0].name == "x"
        ttimeline.disable()
        assert not ttimeline.global_enabled()
        ttimeline.record_global_span("y", 0.0, 0.5)   # no-op
        monkeypatch.setenv("APEX_TPU_TELEMETRY", "1")
        ttimeline._GLOBAL = None
        assert ttimeline.global_enabled()
        assert ttimeline.get_timeline().enabled


class TestTimelineEdgeCases:
    """The ring/span behaviors the fleet merge and flight-recorder
    trace slice lean on, pinned (ISSUE 5 satellite)."""

    def test_wraparound_at_exact_capacity(self):
        tl = telemetry.StepTimeline(capacity=6)
        for _ in range(3):                       # 3 steps x 2 spans = 6
            with tl.step_scope():
                with tl.phase("step"):
                    pass
        summ = tl.summary()
        assert summ["dropped_spans"] == 0 and summ["spans"] == 6
        with tl.step_scope():                    # one more step wraps
            with tl.phase("step"):
                pass
        summ = tl.summary()
        assert summ["spans"] == 6 and summ["dropped_spans"] == 2
        # the summary's step counter keeps counting past the wrap
        assert summ["steps"] == 4
        # oldest spans fell off, newest survived
        assert {s.step for s in tl.spans()} == {1, 2, 3}

    def test_phase_exiting_via_exception_still_records(self):
        tl = telemetry.StepTimeline()
        with pytest.raises(RuntimeError, match="boom"):
            with tl.phase("h2d"):
                raise RuntimeError("boom")
        p = tl.summary()["phases"]["h2d"]
        assert p["count"] == 1 and p["mean_ms"] >= 0.0

    def test_step_scope_exiting_via_exception_closes_step(self):
        tl = telemetry.StepTimeline()
        with pytest.raises(RuntimeError):
            with tl.step_scope():
                raise RuntimeError("mid-step death")
        assert tl.summary()["phases"]["host_step"]["count"] == 1
        # the next scope opens a FRESH step, not a nested one
        with tl.step_scope() as step:
            pass
        assert step == 1

    def test_nested_phases_both_recorded_and_contained(self):
        tl = telemetry.StepTimeline()
        with tl.step_scope():
            with tl.phase("outer"):
                with tl.phase("inner"):
                    pass
        spans = {s.name: s for s in tl.spans()}
        assert {"outer", "inner", "host_step"} <= set(spans)
        # inner exits first (appended first) and nests inside outer
        names = [s.name for s in tl.spans()]
        assert names.index("inner") < names.index("outer")
        inner, outer = spans["inner"], spans["outer"]
        assert outer.t0 <= inner.t0
        assert inner.t0 + inner.dur <= outer.t0 + outer.dur + 1e-9

    def test_export_trace_on_empty_timeline(self, tmp_path):
        tl = telemetry.StepTimeline()
        path = str(tmp_path / "empty.json")
        trace = tl.export_trace(path)
        assert trace["traceEvents"] == []
        with open(path) as f:
            assert json.load(f)["traceEvents"] == []
        # disabled timeline exports empty too (never crashes)
        off = telemetry.StepTimeline(enabled=False)
        assert off.export_trace()["traceEvents"] == []

    def test_export_trace_last_steps_slices(self):
        tl = telemetry.StepTimeline()
        tl.record_span("setup", 0.0, 0.1)        # step -1: kept
        for _ in range(5):
            with tl.step_scope():
                with tl.phase("step"):
                    pass
        trace = tl.export_trace(last_steps=2)
        complete = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        steps = {e["args"]["step"] for e in complete}
        assert steps == {-1, 3, 4}
        full = [e for e in tl.export_trace()["traceEvents"]
                if e["ph"] == "X"]
        assert len(full) == 11

    def test_zero_capacity_ring_never_crashes(self):
        tl = telemetry.StepTimeline(capacity=0)
        with tl.step_scope():
            with tl.phase("step"):
                pass
        assert tl.spans() == []
        assert tl.summary()["dropped_spans"] == 2
        assert tl.export_trace()["traceEvents"] == []

    def test_end_step_without_begin_is_a_noop(self):
        tl = telemetry.StepTimeline()
        tl.end_step()
        assert tl.spans() == []


class TestPrometheusText:
    def test_round_trip_with_labels_and_histograms(self):
        reg = telemetry.registry()
        reg.counter("req_total", "requests").inc(3, code="200")
        reg.counter("req_total").inc(1, code="500")
        reg.gauge("depth", "queue depth").set(2.5)
        h = reg.histogram("lat_s", "latency", buckets=(0.1, 1.0))
        h.observe(0.05, op="save")
        h.observe(5.0, op="save")
        text = reg.to_prometheus_text()
        lines = text.splitlines()
        assert "# HELP req_total requests" in lines
        assert "# TYPE req_total counter" in lines
        assert 'req_total{code="200"} 3' in lines
        assert 'req_total{code="500"} 1' in lines
        assert "# TYPE depth gauge" in lines and "depth 2.5" in lines
        assert "# TYPE lat_s histogram" in lines
        assert 'lat_s_bucket{op="save",le="0.1"} 1' in lines
        assert 'lat_s_bucket{op="save",le="1.0"} 1' in lines
        assert 'lat_s_bucket{op="save",le="+Inf"} 2' in lines
        assert 'lat_s_sum{op="save"} 5.05' in lines
        assert 'lat_s_count{op="save"} 2' in lines
        # one header per metric name even with several series
        assert sum(1 for ln in lines
                   if ln == "# TYPE req_total counter") == 1
        # the snapshot-based renderer (what the dump CLI uses on a
        # bundle from disk) emits the same series lines, empty HELP
        snap_text = tmetrics.prometheus_text_from_snapshot(
            json.loads(json.dumps(reg.snapshot())))
        assert 'req_total{code="200"} 3' in snap_text
        assert 'lat_s_bucket{op="save",le="+Inf"} 2' in snap_text
        assert "# HELP req_total \n# TYPE req_total counter" in snap_text

    def test_module_level_entrypoint(self):
        telemetry.registry().counter("c", "help").inc()
        assert "# HELP c help" in telemetry.to_prometheus_text()
        assert "c 1" in tmetrics.to_prometheus_text(
            {"counters": {"c": 1.0}, "gauges": {}, "histograms": {}})

    def test_empty_registry_renders_empty(self):
        assert telemetry.to_prometheus_text() == ""


class TestCost:
    def test_jitted_cost_on_cpu(self):
        f = jax.jit(lambda x: x @ x)
        x = jnp.ones((32, 32))
        cost = telemetry.cost.jitted_cost(f, x)
        assert cost is not None and cost["flops"] > 0

    def test_normalize_shapes(self):
        norm = telemetry.cost.normalize_cost_analysis
        assert norm({"flops": 1.0}) == {"flops": 1.0}
        assert norm([{"flops": 1.0}]) == {"flops": 1.0}
        assert norm([]) is None
        assert norm(None) is None
        assert norm("nope") is None

    def test_train_step_cost_executes_nothing(self, rng):
        step, state, g = small_step(rng)
        cost = telemetry.cost.train_step_cost(step, state, g)
        assert cost is not None and cost["flops"] > 0
        # state was not donated by the lower() path: still usable
        new_state, _aux = step(state, g)
        assert new_state.space is state.space

    def test_mfu_estimate_reasons(self):
        est = telemetry.cost.mfu_estimate(None, 1.0, kind="TPU v4")
        assert est["mfu"] is None and "no XLA cost model" in est["mfu_reason"]
        est = telemetry.cost.mfu_estimate({"flops": 1e12,
                                           "bytes_accessed": 1e9},
                                          1.0, kind="mystery-chip")
        assert est["mfu"] is None
        assert "no peak-TFLOPs entry" in est["mfu_reason"]
        assert est["hbm_gb_per_sec"] == 1.0
        est = telemetry.cost.mfu_estimate({"flops": 1e12}, 0.0, kind="v4")
        assert est["mfu"] is None and "non-positive" in est["mfu_reason"]

    def test_mfu_estimate_known_chip(self):
        # v4 peak = 275 TFLOP/s: 27.5 TFLOP in 0.1 s -> exactly 1.0 MFU
        est = telemetry.cost.mfu_estimate({"flops": 27.5e12,
                                           "bytes_accessed": None},
                                          0.1, kind="TPU v4")
        assert est["mfu"] == pytest.approx(1.0)
        assert est["mfu_reason"] is None

    def test_publish_mfu_feeds_snapshot_detail(self):
        est = telemetry.cost.mfu_estimate({"flops": 27.5e12,
                                           "bytes_accessed": 4e9},
                                          0.1, kind="TPU v4")
        telemetry.cost.publish_mfu(est)
        det = telemetry.snapshot_detail()
        assert det["mfu"] == pytest.approx(1.0)
        assert "mfu_reason" not in det
        snap = det["registry"]
        assert snap["gauges"]["step_flops"] == 27.5e12
        assert snap["gauges"]["step_hbm_gb_per_sec"] == pytest.approx(40.0)

    def test_snapshot_detail_null_mfu_has_reason(self):
        det = telemetry.snapshot_detail()
        assert det["mfu"] is None and det["mfu_reason"]


class TestTrainStepTelemetry:
    def test_disabled_path_is_the_uninstrumented_object(self, rng):
        from apex_tpu.optimizers.train_step import make_train_step

        step, state, g = small_step(rng)
        # telemetry=None and a disabled timeline return the SAME cached
        # object — the disabled path cannot differ from the seed path
        assert make_train_step(step.opt) is step
        assert make_train_step(step.opt, telemetry=None) is step
        off = telemetry.StepTimeline(enabled=False)
        assert make_train_step(step.opt, telemetry=off) is step
        assert step.with_telemetry(off) is step

    def test_enabled_view_shares_compiled_program(self, rng):
        step, state, g = small_step(rng)
        tl = telemetry.StepTimeline()
        inst = step.with_telemetry(tl)
        assert inst is not step
        assert inst._jitted is step._jitted      # zero recompiles
        assert inst._chained is step._chained
        # the jitted argument list is untouched: lowered text of the
        # instrumented view is byte-identical to the plain step's
        assert (inst.lower(state, g).as_text()
                == step.lower(state, g).as_text())

    def test_step_spans_recorded(self, rng):
        step, state, g = small_step(rng)
        tl = telemetry.StepTimeline(sync=True)
        inst = step.with_telemetry(tl)
        for _ in range(3):
            state, _aux = inst(state, g)
        p = tl.summary()["phases"]["step"]
        assert p["count"] == 3 and p["mean_ms"] >= 0.0

    def test_factory_accepts_telemetry_kwarg(self, rng):
        from apex_tpu.optimizers.train_step import make_train_step

        step, state, g = small_step(rng)
        tl = telemetry.StepTimeline()
        inst = make_train_step(step.opt, telemetry=tl)
        assert inst._telemetry is tl
        assert inst._jitted is step._jitted
        # with_options keeps the attached timeline
        inst2 = inst.with_options(with_grad_norm=True)
        assert inst2._telemetry is tl

class TestInstrumentationPass:
    def test_prefetch_loader_publishes(self):
        from apex_tpu.runtime import PrefetchLoader

        batches = [np.full((2,), i, np.float32) for i in range(4)]
        out = list(PrefetchLoader(iter(batches), depth=2))
        assert len(out) == 4
        reg = telemetry.registry()
        assert reg.counter("prefetch_batches").value() == 4.0
        assert reg.counter("prefetch_device_put_retries").value() == 0.0

    def test_prefetch_retries_counted(self):
        from apex_tpu.resilience import faults
        from apex_tpu.runtime import PrefetchLoader

        batches = [np.full((2,), i, np.float32) for i in range(3)]
        with faults.inject(io_errors={"device_put": frozenset({0, 1})}):
            out = list(PrefetchLoader(iter(batches), depth=2,
                                      retry_base_delay=0.001))
        assert len(out) == 3
        assert telemetry.registry().counter(
            "prefetch_device_put_retries").value() == 2.0

    def test_prefetch_data_wait_spans_when_global_enabled(self):
        from apex_tpu.runtime import PrefetchLoader

        tl = ttimeline.enable(capacity=64)
        batches = [np.full((2,), i, np.float32) for i in range(3)]
        list(PrefetchLoader(iter(batches), depth=2))
        waits = [s for s in tl.spans() if s.name == "data_wait"]
        assert len(waits) >= 3

    def test_checkpoint_save_restore_latency(self, rng, tmp_path,
                                             monkeypatch):
        from apex_tpu import records
        from apex_tpu.resilience import CheckpointManager

        monkeypatch.setattr(records, "RECORDS_DIR",
                            str(tmp_path / "records"))
        step, state, g = small_step(rng)
        mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
        mgr.save(1, state)
        mgr.restore(mgr.path_for(1), template=state)
        reg = telemetry.registry()
        assert reg.counter("checkpoint_saves").value(mode="sync") == 1.0
        snap = reg.snapshot()
        hs = snap["histograms"]['checkpoint_save_seconds{mode="sync"}']
        assert hs["count"] == 1 and hs["sum"] > 0.0
        assert snap["histograms"]["checkpoint_restore_seconds"]["count"] \
            == 1

    def test_corrupt_checkpoint_counted(self, rng, tmp_path, monkeypatch):
        from apex_tpu import records
        from apex_tpu.resilience import CheckpointManager

        monkeypatch.setattr(records, "RECORDS_DIR",
                            str(tmp_path / "records"))
        step, state, g = small_step(rng)
        mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=3)
        mgr.save(1, state)
        mgr.save(2, state)
        # corrupt the newest payload
        p2 = os.path.join(mgr.path_for(2), "payload.bin")
        with open(p2, "r+b") as f:
            f.truncate(8)
        assert mgr.latest_valid() == mgr.path_for(1)
        reg = telemetry.registry()
        assert reg.counter("checkpoint_corrupt_skipped").value() == 1.0
        assert reg.counter("telemetry_events").value(
            event="corrupt_checkpoint") == 1.0

    def test_watchdog_escalation_counted(self, rng, tmp_path, monkeypatch):
        from apex_tpu import records
        from apex_tpu.amp.scaler import LossScaler
        from apex_tpu.resilience import NonfiniteWatchdog

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        scaler = LossScaler(init_scale=2.0 ** 10)
        step, state, g = small_step(rng, scaler=scaler)
        sstate = scaler.init()
        wd = NonfiniteWatchdog(step, manager=None, threshold=2)
        bad = jnp.full_like(g, jnp.nan)
        state, sstate, _ = wd(state, bad, sstate)
        state, sstate, _ = wd(state, bad, sstate)
        reg = telemetry.registry()
        assert reg.counter("resilience_nonfinite_skips").value() == 2.0
        assert reg.counter("resilience_watchdog_escalations").value(
            action="scaler_reset") == 1.0
        assert reg.counter("telemetry_events").value(
            event="nonfinite_escalation") == 1.0

    def test_records_corrupt_skip_event(self, tmp_path, monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        records.write_record("k", {"ok": True}, backend="tpu")
        (tmp_path / "k_99999999T999999Z_dead.json").write_text("{not json")
        rec = records.latest_record("k")
        assert rec["payload"] == {"ok": True}
        reg = telemetry.registry()
        assert reg.counter("records_corrupt_skipped").value() == 1.0
        assert reg.counter("telemetry_events").value(
            event="record_corrupt_skipped") == 1.0

    def test_chip_peak_by_reported_device_kind(self):
        # the string a v5e reports, and its neighbours in the table
        assert tcost.chip_peak_tflops("TPU v5 lite") == 197.0
        assert tcost.chip_peak_tflops("TPU v5p") == 459.0
        assert tcost.chip_peak_tflops("TPU v4") == 275.0
        # an unknown kind is an error on a measurement path ...
        with pytest.raises(ValueError, match="no peak-TFLOPs entry"):
            tcost.chip_peak_tflops("cpu")
        # ... and the telemetry estimate's null-with-reason
        est = tcost.mfu_estimate({"flops": 1e9, "bytes_accessed": 1e6},
                                 1e-3, kind="cpu")
        assert est["mfu"] is None and est["chip_peak_tflops"] is None
        assert "no peak-TFLOPs entry" in est["mfu_reason"]

    def test_timers_publish_into_global_timeline(self):
        from apex_tpu.transformer.pipeline_parallel import Timers

        tl = ttimeline.enable(capacity=32)
        timers = Timers()
        timers("fwd").start()
        timers("fwd").stop()
        spans = [s for s in tl.spans() if s.name == "fwd"]
        assert len(spans) == 1 and spans[0].category == "timers"

    def test_annotate_records_host_span_when_enabled(self):
        from apex_tpu import profiler

        @profiler.annotate("my_region")
        def f(x):
            return x + 1

        assert f(1) == 2                    # timeline off: plain call
        tl = ttimeline.enable(capacity=32)
        assert f(2) == 3
        spans = [s for s in tl.spans() if s.name == "my_region"]
        assert len(spans) == 1 and spans[0].category == "annotate"
