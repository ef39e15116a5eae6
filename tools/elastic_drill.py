"""Elastic resharding drill (one invocation = one "host").

The acceptance scenario of ISSUE 7 / docs/resilience.md "Elastic
resume", run with REAL processes over a real ``jax.distributed``
cluster on CPU (the in-process ``LocalCollective`` simulation lives in
tests/test_elastic.py): kill an N-process run and resume on N−1 and
N+1 processes with the restored state bitwise-identical to an
uninterrupted run.

phase ``train``  — WORLD_SIZE=2: both hosts run a deterministic
    fused-step loop, elastic-checkpointing every 2 steps. The
    orchestrator (tools/check_resilience.sh) sets
    ``APEX_TPU_FAULTS=sigterm=5`` on host 0 ONLY: a real SIGTERM lands
    at step 5, ``should_stop`` spreads it to the fleet by agreement,
    and ``graceful_shutdown`` writes the priority final checkpoint —
    which, through the elastic manager, commits a range-sharded bundle
    WITH a layout manifest. Both hosts exit 0.

phase ``resume`` — ANY world (the orchestrator runs it once with 1
    process and once with 3): every host restores ``latest_valid()``
    through the :class:`ElasticRestorePlanner` (disk reads for its own
    assignment, peer fetches over the collective for the rest),
    proves the reassembled state against the layout fingerprint AND
    across replicas (``ConsistencyGuard.verify_restore``), replays to
    the end, and verifies the final master is bitwise identical to an
    uninterrupted golden run computed locally.

Usage (see check_resilience.sh for the orchestration)::

    MASTER_ADDR=127.0.0.1 MASTER_PORT=29871 WORLD_SIZE=<n> RANK=<r> \\
        python tools/elastic_drill.py {train|resume} <workdir>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a multi-process CPU drill: each rank is one CPU process and none may
# take a chip (set before jax is imported)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

STEPS = 9
CKPT_EVERY = 2
SIGTERM_STEP = 5


def _make(opt):
    import jax.numpy as jnp

    r = np.random.RandomState(0)
    params = {"w": jnp.asarray(r.randn(64, 8), jnp.float32),
              "b": jnp.zeros((8,), jnp.float32)}
    return opt.init(params)


def _grad(space, i):
    import jax.numpy as jnp

    r = np.random.RandomState(1000 + i)
    return jnp.asarray(r.randn(space.total).astype(np.float32) * 0.01)


def _run(step, state, start, stop):
    for i in range(start, stop):
        state, _ = step(state, _grad(state.space, i))
    return state


def main() -> int:
    phase, workdir = sys.argv[1], sys.argv[2]

    from apex_tpu import records
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.train_step import make_train_step
    from apex_tpu.parallel import multiproc
    from apex_tpu.resilience import (ConsistencyGuard, faults,
                                     graceful_shutdown,
                                     install_preemption_handler)

    records.RECORDS_DIR = os.path.join(workdir, "records")
    multiproc.initialize_distributed()          # env-driven, the ref way
    rank, world = multiproc.process_index(), multiproc.world_size()
    col = multiproc.process_collective()
    tag = f"[elastic_drill host {rank}/{world}]"

    opt = FusedAdam(lr=1e-2, impl="xla")
    step = make_train_step(opt)
    state = _make(opt)
    mgr = multiproc.elastic_checkpoint_manager(
        os.path.join(workdir, "ckpt"), keep=4, quorum_timeout=10.0)

    if phase == "train":
        assert world == 2, f"train phase expects WORLD_SIZE=2, got {world}"
        handler = install_preemption_handler()
        for i in range(STEPS):
            state, _ = step(state, _grad(state.space, i))
            if (i + 1) % CKPT_EVERY == 0:
                mgr.save(i + 1, state)
            faults.maybe_sigterm(i + 1)         # host 0's planned SIGTERM
            if handler.should_stop(col):        # agreement: all hosts stop
                graceful_shutdown(mgr, i + 1, state, collective=col,
                                  handler=handler)
                commit = mgr.read_commit(mgr.path_for(i + 1))
                assert commit.get("layout") is not None, (
                    f"{tag} graceful_shutdown committed WITHOUT a layout "
                    "manifest — the elastic wiring is broken")
                assert i + 1 == SIGTERM_STEP, (tag, i + 1)
                print(f"{tag} preempted at step {i + 1}, elastic bundle "
                      f"committed (world {commit['layout']['world']})",
                      flush=True)
                return 0
        raise SystemExit(f"{tag} survived a drill that SIGTERMs host 0")

    assert phase == "resume", phase
    path = mgr.latest_valid()
    assert path == mgr.path_for(SIGTERM_STEP), (
        f"{tag} resumed from {path}, wanted the elastic step-"
        f"{SIGTERM_STEP} bundle")
    restored = mgr.restore(path, template=state, collective=col)
    assert restored.step == SIGTERM_STEP
    guard = ConsistencyGuard(step, collective=col, fingerprint_every=2)
    guard.verify_restore(restored.opt_state,
                         baseline=restored.fingerprint)
    state = _run(step, restored.opt_state, restored.step, STEPS)

    golden = _run(step, _make(opt), 0, STEPS)
    if not np.array_equal(np.asarray(state.master),
                          np.asarray(golden.master)):
        raise SystemExit(f"{tag} resumed trajectory diverged from golden")
    fetched = sum(1 for s in restored.plan["ranges"]
                  if str(s.get("source", "")).startswith("peer_"))
    print(f"{tag} resumed saved-world {restored.plan['saved_world']} on "
          f"world {world} ({fetched} ranges fetched over the "
          "collective), replay bitwise-identical: OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
