"""Two-process fleet-observability drill (one invocation = one "host").

The flight-recorder acceptance scenario of docs/observability.md run
with REAL processes over a real ``jax.distributed`` cluster on CPU
(pattern of tools/quorum_drill.py; the in-process threaded analog
lives in tests/test_flight.py): the orchestrator
(tools/check_observability.sh) injects a one-replica ``bit_flip``
fault on host 1 via ``APEX_TPU_FAULTS``, both hosts run a
guard-wrapped fused-step loop with the global timeline on and the
global flight recorder armed with a ``ProcessCollective``, and the
divergence boundary must:

1. detect the flip and repair it — with TWO hosts a 1v1 split has no
   majority, so the guard takes the no-quorum path: both hosts roll
   back to the last QUORUM checkpoint (the PR-3 contract), AND
2. dump a committed ``flightrec_*.json`` black box on EVERY host whose
   - ``trigger`` is ``replica_divergence``,
   - fleet snapshot sums both hosts' counters (pinned against this
     host's own registry snapshot in the same bundle),
   - straggler gauges are present (host 1 carries an injected per-step
     sleep so the spread is real),
   - perfetto trace slice parses as well-formed Chrome-trace JSON.

After the loop both hosts verify the repair end state is bitwise
identical across the fleet (an all-gather of the master buffer).

The drill then exercises the COMMS plane (docs/observability.md
"Comms & sharding plane"): the loop above ran with the comms tracer
armed, so every guard gather/agree and quorum barrier crossed the
instrumented ``KVStoreCollective`` — both hosts assert
``collective_ops{...impl="KVStoreCollective"}`` counters and
``collective:*`` timeline spans, warm the barrier EWMA and latch a
``collective_slow`` escalation through the documented
``collective_slow=<ms>`` fault clause, and merge both hosts'
timelines into ONE offset-corrected perfetto trace
(``fleet.export_fleet_trace``; host 0 commits it to
``<workdir>/merged_trace.json`` for the orchestrator to validate).

Usage (see check_observability.sh for the orchestration)::

    MASTER_ADDR=127.0.0.1 MASTER_PORT=29881 WORLD_SIZE=2 RANK=<r> \\
        [APEX_TPU_FAULTS="bit_flip=3;bit_flip_replica=1;bit_flip_leaf=0"] \\
        python tools/fleet_drill.py <workdir>
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a multi-process CPU drill: each rank is one CPU process and none may
# take a chip (set before jax is imported)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

STEPS = 8
FP_EVERY = 2
FLIP_STEP = 3          # strictly inside a fingerprint window
STRAGGLER_RANK = 1
STRAGGLE_S = 0.04    # big enough to dominate OS sleep granularity


def main() -> int:
    workdir = sys.argv[1]

    import jax.numpy as jnp

    from apex_tpu import records, telemetry
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.train_step import make_train_step
    from apex_tpu.parallel import multiproc
    from apex_tpu.resilience import (CheckpointManager, ConsistencyGuard,
                                     faults)
    from apex_tpu.telemetry import comms, flight
    from apex_tpu.telemetry import fleet as fleet_mod

    multiproc.initialize_distributed()          # env-driven, the ref way
    rank, world = multiproc.process_index(), multiproc.world_size()
    assert world == 2, f"drill expects WORLD_SIZE=2, got {world}"
    tag = f"[fleet_drill host {rank}]"
    # per-host records dir: each host's black box is asserted against
    # its own registry, and O_EXCL claims never race across hosts
    records.RECORDS_DIR = os.path.join(workdir, f"records_{rank}")

    # arm the comms tracer BEFORE the collective is built, so
    # process_collective() hands back the instrumented wrapper
    comms.enable()
    col = multiproc.process_collective()
    assert col.n_replicas == 2
    assert isinstance(col, comms.InstrumentedCollective), type(col)
    assert col.impl_name() == "KVStoreCollective", col.impl_name()

    tl = telemetry.enable(capacity=512)
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep=4,
                            process_id=rank, n_processes=world,
                            quorum_timeout=30.0)
    recorder = flight.enable(collective=col, manager=mgr, keep=3,
                             last_steps=STEPS)

    opt = FusedAdam(lr=1e-2, impl="xla")
    step = make_train_step(opt, fingerprint_every=FP_EVERY, telemetry=tl)
    guard = ConsistencyGuard(step, collective=col, manager=mgr)

    r = np.random.RandomState(0)
    params = {"w": jnp.asarray(r.randn(64, 8), jnp.float32),
              "b": jnp.zeros((8,), jnp.float32)}
    st = opt.init(params)
    reg = telemetry.registry()

    for i in range(STEPS):
        reg.counter("drill_steps", "fused steps this host ran").inc()
        with tl.step_scope():
            with tl.phase("data_wait"):
                # a deterministic straggle on host 1 so the fleet
                # data_wait spread is real, not timing noise
                time.sleep(STRAGGLE_S if rank == STRAGGLER_RANK
                           else STRAGGLE_S / 8)
            st = st._replace(master=faults.flip_bits(
                st.master, i, replica=rank, space=st.space))
            r2 = np.random.RandomState(1000 + i)
            g = jnp.asarray(r2.randn(st.space.total).astype(np.float32)
                            * 0.01)
            st, _aux = guard(st, g)
        if (i + 1) % FP_EVERY == 0:
            mgr.save(i + 1, st)                 # quorum checkpoints

    # -- detection resolved by rollback (1v1: no majority to repair
    # from) and the fleet left the run bit-identical
    assert guard.rollbacks == 1, \
        f"{tag} expected 1 rollback, saw {guard.rollbacks}"
    masters = col.all_gather(np.asarray(st.master))
    if not np.array_equal(masters[0], masters[1]):
        raise SystemExit(f"{tag} post-repair masters differ across hosts")

    # -- the black box landed, committed, with the divergence trigger
    assert recorder.dumps >= 1, f"{tag} flight recorder never dumped"
    rec = records.latest_record("flightrec", require_backend=None)
    assert rec is not None, f"{tag} no flightrec record on disk"
    bundle = rec["payload"]
    assert bundle["trigger"] == "replica_divergence", bundle["trigger"]
    assert bundle["n_replicas"] == 2 and bundle["replica_id"] == rank
    assert bundle["faults"] == os.environ.get("APEX_TPU_FAULTS"), \
        f"{tag} bundle lost the faults config"
    # the bundle names the checkpoint a resume would use: at dump time
    # (inside the divergence boundary, before the rollback restore)
    # that is the step-2 quorum checkpoint
    lc = bundle["last_checkpoint"]
    assert lc and lc.get("step") == FLIP_STEP - 1, \
        f"{tag} bundle last_checkpoint {lc} != quorum step {FLIP_STEP - 1}"

    # fleet snapshot sums host counters: pinned against this host's own
    # registry snapshot carried in the SAME bundle (both hosts were at
    # the same loop point when their snapshots were gathered)
    fleet = bundle["fleet"]
    assert fleet is not None and fleet["n_hosts"] == 2, \
        f"{tag} bundle has no fleet snapshot"
    local_steps = bundle["telemetry"]["registry"]["counters"]["drill_steps"]
    fleet_steps = fleet["counters"]["drill_steps"]
    assert fleet_steps == world * local_steps, (
        f"{tag} fleet counter {fleet_steps} != {world} x local "
        f"{local_steps}")

    # straggler gauges present (published by the dump's aggregation
    # BEFORE the local snapshot was taken) and the spread is real
    gauges = bundle["telemetry"]["registry"]["gauges"]
    spread_keys = [k for k in gauges
                   if k.startswith("fleet_straggler_spread")]
    assert spread_keys, f"{tag} no fleet_straggler_spread gauge in bundle"
    strag = fleet["straggler"]["phases"]
    assert "step" in strag and strag["step"].get("spread") is not None, \
        f"{tag} fleet snapshot carries no step-phase spread"
    # the injected data_wait straggle shows in the fleet spread
    dw_spread = strag["data_wait"].get("spread")
    assert dw_spread is not None and dw_spread > 2.0, \
        f"{tag} injected data_wait straggle invisible (spread={dw_spread})"

    # the perfetto slice parses: well-formed Chrome-trace JSON
    trace = bundle["trace"]
    assert trace is not None, f"{tag} bundle has no trace slice"
    json.loads(json.dumps(trace))               # round-trips as JSON
    events = trace["traceEvents"]
    complete = [e for e in events if e.get("ph") == "X"]
    assert complete, f"{tag} trace slice has no complete events"
    for e in complete:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    assert any(e["name"] == "host_step" for e in complete)

    # state digests rode the boundary checksums
    assert bundle["state_digests"], f"{tag} no state digests retained"
    assert all("xor" in d and "step" in d for d in bundle["state_digests"])

    # -- comms plane: the loop's gathers/agrees/barriers all crossed
    # the instrumented collective on this host
    counters = reg.snapshot()["counters"]
    kv_ops = {k: v for k, v in counters.items()
              if k.startswith("collective_ops")
              and 'impl="KVStoreCollective"' in k}
    assert kv_ops and sum(kv_ops.values()) > 0, \
        f"{tag} no traced collective ops on this host"
    c_spans = [s for s in tl.spans() if s.category == "collective"]
    assert c_spans and all(s.name.startswith("collective:")
                           for s in c_spans), \
        f"{tag} no collective:* spans in the timeline"
    # the bundle carried the comms section (armed -> the full summary)
    assert bundle["comms"]["enabled"] is True, \
        f"{tag} flight bundle lost the comms section"
    assert any(r["op"] == "all_gather" and r["calls"] > 0
               for r in bundle["comms"]["ledger"]), \
        f"{tag} bundle ledger has no all_gather row"

    # escalation drill: warm the barrier EWMA past min_samples, then
    # inject a delay through the DOCUMENTED clause grammar on both
    # hosts — the next barrier must latch one collective_slow event
    tr = comms.get_tracer()
    for _ in range(tr.min_samples + 1):
        col.barrier()
    ewma = tr.op_stats()["barrier"]["ewma_ms"]
    delay_ms = max(60.0, tr.slow_factor * 2.0 * ewma)
    faults.install(faults.FaultInjector.from_env(
        f"collective_slow={delay_ms:.3f}"))
    try:
        col.barrier()
    finally:
        faults.install(None)        # back to the env-driven plan
    counters = reg.snapshot()["counters"]
    assert counters.get('collective_slow_total{op="barrier"}', 0) >= 1, \
        f"{tag} injected {delay_ms:.1f}ms barrier delay never escalated"
    assert counters.get('telemetry_events{event="collective_slow"}',
                        0) >= 1, f"{tag} no collective_slow event"
    assert any(e.get("event") == "collective_slow"
               for e in recorder.events), \
        f"{tag} collective_slow missing from the flight ring"

    # merged fleet trace: one offset-corrected perfetto timeline, both
    # hosts' spans + the escalation instants; host 0 commits the file
    trace_path = (os.path.join(workdir, "merged_trace.json")
                  if rank == 0 else None)
    merged = fleet_mod.export_fleet_trace(col, path=trace_path)
    evs = merged["traceEvents"]
    complete_pids = {e["pid"] for e in evs if e.get("ph") == "X"}
    assert complete_pids == {0, 1}, \
        f"{tag} merged trace pids {complete_pids} != both hosts"
    for r in (0, 1):
        c_evs = [e for e in evs if e.get("ph") == "X" and e["pid"] == r
                 and e["name"].startswith("collective:")]
        assert c_evs, \
            f"{tag} merged trace has no collective spans for host {r}"
        # every collective span carries its bytes/ms attribution
        assert all("payload_bytes" in e["args"] and e["dur"] >= 0
                   for e in c_evs), \
            f"{tag} host {r} collective spans lost bytes attribution"
        assert any(e.get("ph") == "M" and e["name"] == "process_name"
                   and e["pid"] == r for e in evs), \
            f"{tag} merged trace lacks host {r} process_name track"
    assert any(e.get("ph") == "i" and e["name"] == "collective_slow"
               for e in evs), \
        f"{tag} merged trace lacks the collective_slow instant"
    assert all(e["ts"] >= 0 for e in evs if "ts" in e), \
        f"{tag} merged trace has negative ts after normalization"
    n_hosts_merged = merged["otherData"]["n_hosts"]
    assert n_hosts_merged == 2, f"{tag} merged {n_hosts_merged} hosts"

    print(f"{tag} comms plane OK: {int(sum(kv_ops.values()))} traced "
          f"ops, {len(c_spans)} collective spans, clock spread="
          f"{merged['otherData']['clock_offset_spread_ms']}ms, "
          f"{len(evs)} merged trace events", flush=True)
    print(f"{tag} divergence black box OK: trigger="
          f"{bundle['trigger']}, fleet drill_steps={fleet_steps}, "
          f"straggler spread={strag['step']['spread']}, "
          f"{len(complete)} trace events", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
