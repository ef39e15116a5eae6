#!/usr/bin/env bash
# Observability smoke (CI / pre-merge, next to check_telemetry.sh and
# check_resilience.sh): the fleet-aggregation / flight-recorder /
# compile-tracker / devmem / records unit tier, the
# disabled-telemetry structural guarantee (the disabled path IS the
# cached raw step object), the COMPILE-TRACKER smoke (one forced
# re-trace of the train step must emit exactly ONE `recompile` event
# with a signature diff, cache hits must publish nothing, and the
# armed tracker must hold the <1% steady-state overhead budget), and
# the two-process jax.distributed FLEET DRILL (tools/fleet_drill.py):
# a one-replica bit_flip injected via APEX_TPU_FAULTS must produce a
# committed flightrec_*.json black box on every host — trigger
# replica_divergence, fleet snapshot summing both hosts' counters,
# straggler gauges present, perfetto slice well-formed — plus the
# COMMS-PLANE smoke (docs/observability.md "Comms & sharding plane"):
# disabled means instrument(col) IS col (zero wrapper), and the drill
# must assert collective spans on both hosts, latch a collective_slow
# escalation from the injected-delay fault clause, and commit ONE
# offset-corrected merged perfetto trace this script structure-
# validates. Extra args pass through to pytest.
set -uo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

rc=0

python -m pytest tests/test_telemetry.py tests/test_fleet.py \
    tests/test_flight.py \
    tests/test_records.py tests/test_compiled.py tests/test_devmem.py \
    tests/test_comms.py tests/test_goodput.py \
    "$@" -q -p no:cacheprovider || rc=1

echo "== compile-tracker smoke: one forced retrace =="
python - <<'PY' || rc=1
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import telemetry
from apex_tpu.telemetry import compiled
from apex_tpu.optimizers import FusedAdam
from apex_tpu.optimizers.train_step import make_train_step

telemetry.reset()
sink = telemetry.InMemorySink()
telemetry.registry().add_sink(sink)
compiled.enable()

rng = np.random.RandomState(0)
params = {f"p{i}": jnp.asarray(rng.randn(512).astype(np.float32) * 0.02)
          for i in range(12)}
opt = FusedAdam(lr=1e-3)
state = opt.init(params)
g = jnp.asarray(rng.randn(state.space.total).astype(np.float32) * 1e-3)

step = make_train_step(opt)
state, _ = step(state, g)                 # first trace+compile
assert not [e for e in sink.events if e["event"] == "recompile"], \
    "the FIRST signature is a compile, not a recompile"
compiles = telemetry.registry().counter("compile_count").value(
    fn="train_step")
assert compiles >= 1, "labeled compile not recorded"
state, _ = step(state, g)                 # layout cache hit
assert telemetry.registry().counter("compile_count").value(
    fn="train_step") == compiles, "a cache hit must publish no compile"

# forced re-trace: ONE changed static option on the same fn
sibling = step.with_options(with_grad_norm=True)
state, _ = sibling(state, g)
rec = [e for e in sink.events if e["event"] == "recompile"]
assert len(rec) == 1, f"expected exactly one recompile event, got {rec}"
assert rec[0]["fn"] == "train_step"
assert "with_grad_norm" in rec[0]["signature_diff"]["changed"], rec[0]
state, _ = sibling(state, g)              # hit on the sibling: still one
assert len([e for e in sink.events if e["event"] == "recompile"]) == 1

# re-assert the structural guarantees with the tracker ARMED: the
# disabled-telemetry path is still the raw cached step object...
assert make_train_step(opt, telemetry=None) is step
assert make_train_step(
    opt, telemetry=telemetry.StepTimeline(enabled=False)) is step

# ...and the armed tracker adds <1% to the steady-state host loop
# (layout hits never reach the tracker; this measures exactly that)
STEPS = 20

def loop(s, st):
    for _ in range(STEPS):
        st, _aux = s(st, g)
    jax.block_until_ready(st.master)
    return st

state = loop(step, state)                 # warm
t_on = t_off = float("inf")
for _ in range(11):                       # interleaved best-of
    compiled.enable()
    t0 = time.perf_counter()
    state = loop(step, state)
    t_on = min(t_on, time.perf_counter() - t0)
    compiled.disable()
    t0 = time.perf_counter()
    state = loop(step, state)
    t_off = min(t_off, time.perf_counter() - t0)
overhead = t_on / t_off - 1.0
print(f"tracker-armed={t_on * 1e3:.3f}ms disarmed={t_off * 1e3:.3f}ms "
      f"overhead={overhead * 100:+.3f}%")
assert overhead < 0.01, (
    f"armed compile-tracker steady-state overhead "
    f"{overhead * 100:.3f}% >= 1%")
compiled.disable()
telemetry.reset()
print("compile-tracker smoke: OK")
PY

echo "== disabled-telemetry structural guarantee =="
python - <<'PY' || rc=1
from apex_tpu import telemetry
from apex_tpu.optimizers import FusedAdam
from apex_tpu.optimizers.train_step import make_train_step

import jax.numpy as jnp
import numpy as np

rng = np.random.RandomState(0)
params = {"w": jnp.asarray(rng.randn(256).astype(np.float32))}
opt = FusedAdam(lr=1e-3)
step = make_train_step(opt)
disabled = make_train_step(
    opt, telemetry=telemetry.StepTimeline(enabled=False))
# the <1% overhead budget of check_telemetry.sh rests on this identity:
# with telemetry disabled there is NO instrumented code to be slow —
# the flight-recorder / fleet wiring must not have broken it
assert disabled is step, "disabled telemetry must be the raw step object"
assert make_train_step(opt, telemetry=None) is step
# and an armed-then-disarmed flight recorder leaves it intact
telemetry.flight.enable(keep=1)
telemetry.flight.disable()
assert make_train_step(opt, telemetry=None) is step
print("disabled-is-step: OK")
PY

echo "== comms-plane structural guarantee =="
python - <<'PY' || rc=1
import numpy as np

from apex_tpu import telemetry
from apex_tpu.telemetry import comms
from apex_tpu.resilience.guard import NullCollective

telemetry.reset()
# disabled means UNTOUCHED: the raw object, no wrapper in the path —
# the make_train_step disabled-is-step discipline applied to the wire
col = NullCollective()
assert comms.instrument(col) is col, \
    "disarmed instrument() must return the exact object passed in"
assert not comms.enabled()

# armed: the same call wraps, ops land on the registry, and the
# bundle section flips from reason to summary
tracer = comms.enable()
wrapped = comms.instrument(col)
assert isinstance(wrapped, comms.InstrumentedCollective)
assert comms.instrument(wrapped) is wrapped, "re-wrap must be idempotent"
out = wrapped.all_gather(np.ones(256, np.float32))
assert np.array_equal(np.asarray(out)[0], np.ones(256, np.float32))
wrapped.barrier()
snap = telemetry.registry().snapshot()["counters"]
key = 'collective_ops{impl="NullCollective",op="all_gather"}'
assert snap.get(key) == 1.0, snap
assert comms.section()["enabled"] is True
ledger = {r["op"]: r for r in tracer.ledger()}
assert ledger["all_gather"]["payload_bytes"] == 1024
assert ledger["all_gather"]["wire_bytes"] == 1024  # n_replicas == 1
telemetry.reset()
assert comms.section()["enabled"] is False, \
    "reset must disarm the comms plane"
print("comms structural guarantees: OK")
PY

# Goodput kill-and-resume drill (docs/observability.md "Run ledger &
# goodput"): a 30-step run with injected data stalls (the
# data_stall_ms fault clause), one forced watchdog rollback, and a
# real SIGTERM -> graceful drain; invocation 2 resumes from the
# drained checkpoint (the packed ledger rides the manifest extra),
# asserts every exercised bucket is nonzero, the attribution identity
# holds, and the unattributed residual stays under 5% of wall — then
# the report CLI renders the table from the checkpoint dir ALONE (the
# dead-run postmortem path, docs/resilience.md "Postmortem runbook").
echo "== goodput kill-and-resume drill =="
gp_dir="$(mktemp -d)"
cat > "$gp_dir/goodput_drill.py" <<'PY'
import json
import os
import sys

sys.path.insert(0, os.getcwd())   # invoked from the repo root

import jax.numpy as jnp
import numpy as np

from apex_tpu import telemetry
from apex_tpu.amp.scaler import LossScaler
from apex_tpu.optimizers import FusedAdam
from apex_tpu.optimizers.train_step import make_train_step
from apex_tpu.resilience import CheckpointManager, NonfiniteWatchdog, faults
from apex_tpu.resilience.guard import (graceful_shutdown,
                                       install_preemption_handler)
from apex_tpu.runtime import PrefetchLoader

ckpt_dir, phase = sys.argv[1], sys.argv[2]

telemetry.reset()
goodput = telemetry.goodput

rng = np.random.RandomState(0)
params = {"w1": jnp.asarray(rng.randn(64, 32).astype(np.float32) * 0.02),
          "b": jnp.zeros((32,), jnp.float32)}
opt = FusedAdam(lr=1e-3, impl="xla")
scaler = LossScaler(init_scale=2.0 ** 8, scale_window=100)
step_fn = make_train_step(
    opt, scaler=scaler,
    # sync=True: the span covers device execution, not just dispatch,
    # so the per-step compute lands in productive instead of leaking
    # into unattributed at the watchdog's found_inf sync
    telemetry=telemetry.StepTimeline(enabled=True, sync=True))
state = opt.init(params)
sstate = scaler.init()
mgr = CheckpointManager(f"{ckpt_dir}", keep=8)
wd = NonfiniteWatchdog(step_fn, manager=mgr, threshold=1)
base_g = jnp.asarray(rng.randn(state.space.total).astype(np.float32) * 1e-3)
nan_g = jnp.asarray(base_g).at[0].set(float("nan"))  # pre-built: the
# scatter's compile is drill scaffolding, not run time to attribute
handler = install_preemption_handler()

# arm AFTER setup: the ledger's wall starts here, so import/init time
# (not part of any run) stays out of the unattributed residual
goodput.enable(publish_every=10)

start = 0
if phase == "resume":
    restored = mgr.restore(template=state)   # absorbs the packed ledger
    state, sstate = restored.opt_state, restored.scaler_state
    start = restored.step + 1
n_steps = 10 if phase == "resume" else 30


def batches(n):
    for _ in range(n):
        yield rng.randn(128).astype(np.float32)


for j, b in enumerate(PrefetchLoader(batches(n_steps), depth=2)):
    i = start + j
    g = base_g
    if phase == "first" and i == 8:
        g = nan_g                            # -> threshold=1 rollback
    state, sstate, aux = wd(state, g, sstate)
    goodput.observe_step(step=i, loss=1.0 / (i + 1.0), tokens=2048)
    if i and i % 5 == 0:
        mgr.save(i, state, scaler_state=sstate)
    faults.maybe_sigterm(i)                  # sigterm=20 in phase one
    if handler.should_stop():
        graceful_shutdown(mgr, i, state, scaler_state=sstate,
                          handler=handler)
        print("phase1 drained at step", i)
        sys.exit(0)

if phase == "first":
    sys.exit("phase one must end in the SIGTERM drain, not fall through")

mgr.save(start + n_steps - 1, state, scaler_state=sstate)
s = goodput.get_ledger().summary()
sec = s["seconds"]
assert s["restarts"] == 1, s
assert s["rollbacks"] == 0, "the rollback happened in phase one"
for cause in ("productive", "data_wait", "checkpoint_save",
              "checkpoint_restore", "rollback", "rework",
              "drain_shutdown"):
    assert sec[cause] > 0.0, (cause, sec)
assert s["rework_steps"] > 0, s
attributed = sum(v for c, v in sec.items() if c != "unattributed")
wall = s["wall_seconds"]
# the identity: buckets + residual == wall (or == buckets themselves
# when async overlap pushed attribution past wall and residual is 0)
assert abs(attributed + sec["unattributed"] - max(wall, attributed)) < 1e-3, s
assert sec["unattributed"] < 0.05 * wall, (
    f"unattributed {sec['unattributed']:.3f}s >= 5% of wall {wall:.3f}s")
print("resume summary:", json.dumps(
    {k: s[k] for k in ("restarts", "rework_steps", "goodput_fraction",
                       "unattributed_seconds", "wall_seconds")}))
PY
if env APEX_TPU_FAULTS="data_stall_ms=4;sigterm=20" \
        python "$gp_dir/goodput_drill.py" "$gp_dir/ckpt" first \
        && python "$gp_dir/goodput_drill.py" "$gp_dir/ckpt" resume; then
    # the postmortem path: the table renders from the dir ALONE, and
    # carries the restart the resumed incarnation recorded (captured,
    # not piped into grep -q: an early-exiting reader would SIGPIPE
    # the report under pipefail even on a match)
    gp_report="$(python tools/goodput_report.py "$gp_dir/ckpt")"
    if grep -q "^restarts    1" <<<"$gp_report"; then
        echo "goodput kill-and-resume drill: OK"
    else
        echo "goodput drill FAILED: report from checkpoint dir lacks" \
             "the resumed restart" >&2
        printf '%s\n' "$gp_report" >&2
        rc=1
    fi
else
    echo "goodput drill FAILED" >&2
    rc=1
fi
rm -rf "$gp_dir"

echo "== goodput ledger overhead budget =="
python - <<'PY' || rc=1
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import telemetry
from apex_tpu.optimizers import FusedAdam
from apex_tpu.optimizers.train_step import make_train_step

telemetry.reset()
rng = np.random.RandomState(0)
# ~2ms CPU step — the granularity the <1% budget is stated against
# (docs/observability.md "Run ledger & goodput")
params = {f"p{i}": jnp.asarray(rng.randn(24576).astype(np.float32) * 0.02)
          for i in range(12)}
opt = FusedAdam(lr=1e-3)
state = opt.init(params)
g = jnp.asarray(rng.randn(state.space.total).astype(np.float32) * 1e-3)
# the SAME instrumented step both ways: armed-vs-disarmed measures
# exactly the ledger's span observer + per-step feed, nothing else.
# sync=True: each step blocks, so the comparison isolates the
# ledger's host work instead of the CPU backend's GIL/thread
# scheduling interaction with async dispatch
step = make_train_step(
    opt, telemetry=telemetry.StepTimeline(enabled=True, sync=True))
STEPS = 20

def loop(s, st):
    for k in range(STEPS):
        st, _aux = s(st, g)
        telemetry.goodput.observe_step(step=k, loss=1.0, tokens=512)
    jax.block_until_ready(st.master)
    return st

state = loop(step, state)                 # warm
t_on = t_off = float("inf")
for _ in range(11):                       # interleaved best-of
    telemetry.goodput.enable(publish_every=10 ** 9)
    t0 = time.perf_counter()
    state = loop(step, state)
    t_on = min(t_on, time.perf_counter() - t0)
    telemetry.goodput.disable()
    t0 = time.perf_counter()
    state = loop(step, state)
    t_off = min(t_off, time.perf_counter() - t0)
overhead = t_on / t_off - 1.0
print(f"ledger-armed={t_on * 1e3:.3f}ms disarmed={t_off * 1e3:.3f}ms "
      f"overhead={overhead * 100:+.3f}%")
assert overhead < 0.01, (
    f"armed goodput-ledger steady-state overhead "
    f"{overhead * 100:.3f}% >= 1%")
telemetry.reset()
print("goodput overhead budget: OK")
PY

# Two-process jax.distributed fleet drill: rank 1 carries the bit_flip
# fault; both hosts must leave a committed flight bundle (see
# tools/fleet_drill.py for every asserted property).
echo "== two-process fleet drill =="
drill_dir="$(mktemp -d)"
drill_port=$(( 20000 + RANDOM % 20000 ))
drill_env=(MASTER_ADDR=127.0.0.1 "MASTER_PORT=$drill_port" WORLD_SIZE=2)
env "${drill_env[@]}" RANK=0 python tools/fleet_drill.py "$drill_dir" &
h0=$!
env "${drill_env[@]}" RANK=1 \
    APEX_TPU_FAULTS="bit_flip=3;bit_flip_replica=1;bit_flip_leaf=0" \
    python tools/fleet_drill.py "$drill_dir" &
h1=$!
wait $h0; rc0=$?
wait $h1; rc1=$?
if [ "$rc0" -ne 0 ] || [ "$rc1" -ne 0 ]; then
    echo "fleet drill FAILED (host0 rc=$rc0, host1 rc=$rc1)" >&2
    rc=1
else
    # the bundle's perfetto slice + registry snapshot feed the dump CLI
    bundle="$(ls "$drill_dir"/records_0/flightrec_*.json | head -1)"
    if python tools/telemetry_dump.py "$bundle" | grep -q "drill_steps"; then
        echo "two-process fleet drill: OK"
    else
        echo "fleet drill FAILED: telemetry_dump found no drill_steps" \
             "in $bundle" >&2
        rc=1
    fi
    # the armed comms plane rode the same bundle: the dump CLI's prom
    # view must render collective_ops series + the comms summary line
    dump="$(python tools/telemetry_dump.py "$bundle")"
    if echo "$dump" | grep -q '^collective_ops{' \
            && echo "$dump" | grep -Eq '^# comms: [0-9]+ collective ops'; then
        echo "bundle comms section: OK"
    else
        echo "fleet drill FAILED: bundle dump carries no comms plane" >&2
        rc=1
    fi
    # host 0 committed the offset-corrected merged perfetto trace;
    # hold it to the structure the drill promised
    python - "$drill_dir/merged_trace.json" <<'PY' || rc=1
import json
import sys

with open(sys.argv[1]) as f:
    trace = json.load(f)
evs = trace["traceEvents"]
pids = {e["pid"] for e in evs if e.get("ph") == "X"}
assert pids == {0, 1}, f"merged trace pids {pids}: want both hosts"
for r in (0, 1):
    c_evs = [e for e in evs if e.get("ph") == "X" and e["pid"] == r
             and e["name"].startswith("collective:")]
    assert c_evs, f"no collective spans for host {r}"
    assert all("payload_bytes" in e["args"] and e["dur"] >= 0
               for e in c_evs), f"host {r} spans lack bytes attribution"
    names = [e for e in evs if e.get("ph") == "M"
             and e["name"] == "process_name" and e.get("pid") == r]
    assert names, f"no process_name track for host {r}"
assert any(e.get("ph") == "i" and e["name"] == "collective_slow"
           for e in evs), "no collective_slow instant in merged trace"
assert all(e["ts"] >= 0 for e in evs if "ts" in e), "negative ts"
od = trace["otherData"]
assert od["n_hosts"] == 2 and "clock_offsets_ms" in od
print(f"merged fleet trace: OK ({len(evs)} events, "
      f"clock spread {od['clock_offset_spread_ms']}ms)")
PY
fi
rm -rf "$drill_dir"

if [ "$rc" -eq 0 ]; then
    echo "check_observability: OK"
else
    echo "check_observability: FAILED (rc=$rc)" >&2
fi
exit $rc
