"""Two-process quorum-checkpoint drill (one invocation = one "host").

The distributed acceptance scenario of docs/resilience.md run with
REAL processes over a real ``jax.distributed`` cluster on CPU — the
in-process threaded simulation lives in tests/test_quorum_checkpoint.py;
this drill proves the same protocol across actual process boundaries,
driven purely by the launcher env conventions
(MASTER_ADDR/MASTER_PORT/WORLD_SIZE/RANK -> multiproc.
initialize_distributed) and the ``APEX_TPU_FAULTS`` env knob:

phase ``train``  — both hosts run a deterministic fused-step loop,
    quorum-checkpointing every 2 steps. The orchestrator (tools/
    check_resilience.sh) sets ``APEX_TPU_FAULTS=crash_before_commit=6``
    on host 1 ONLY: host 1 dies inside its step-6 save before its
    shard lands (exit 42, the expected death), and host 0's
    coordinator commit times out (``CheckpointError``, exit 0 after
    verifying the step-6 set stayed uncommitted).

phase ``resume`` — both hosts come back, restore
    ``latest_valid()`` — which MUST be the step-4 QUORUM checkpoint,
    never the partial step-6 host-set — replay to the end, and verify
    the final master is bitwise identical to an uninterrupted golden
    run computed locally.

Usage (see check_resilience.sh for the orchestration)::

    MASTER_ADDR=127.0.0.1 MASTER_PORT=29871 WORLD_SIZE=2 RANK=<r> \\
        python tools/quorum_drill.py {train|resume} <workdir>
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
CRASH_STEP = 6
QUORUM_STEP = 4


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
    from apex_tpu.resilience import (CheckpointError, CheckpointManager,
                                     SimulatedCrash)

    records.RECORDS_DIR = os.path.join(workdir, "records")
    multiproc.initialize_distributed()          # env-driven, the ref way
    rank, world = multiproc.process_index(), multiproc.world_size()
    assert world == 2, f"drill expects WORLD_SIZE=2, got {world}"
    tag = f"[quorum_drill host {rank}]"

    opt = FusedAdam(lr=1e-2, impl="xla")
    step = make_train_step(opt)
    state = _make(opt)
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep=4,
                            process_id=rank, n_processes=world,
                            quorum_timeout=10.0)

    if phase == "train":
        try:
            for i in range(STEPS):
                state, _ = step(state, _grad(state.space, i))
                if (i + 1) % CKPT_EVERY == 0:
                    mgr.save(i + 1, state)
        except SimulatedCrash as e:
            print(f"{tag} died as planned: {e}", flush=True)
            return 42                           # the expected death
        except CheckpointError as e:
            assert "quorum timeout" in str(e), e
            ok, reason = mgr.validate(mgr.path_for(CRASH_STEP))
            assert not ok and "commit" in reason, (ok, reason)
            print(f"{tag} coordinator refused the partial host-set: "
                  f"{reason}", flush=True)
            return 0
        raise SystemExit(f"{tag} survived a drill that kills host 1")

    assert phase == "resume", phase
    path = mgr.latest_valid()
    assert path == mgr.path_for(QUORUM_STEP), (
        f"{tag} resumed from {path}, wanted the step-{QUORUM_STEP} "
        "QUORUM checkpoint")
    restored = mgr.restore(path, template=state)
    assert restored.step == QUORUM_STEP
    state = _run(step, restored.opt_state, restored.step, STEPS)

    golden = _run(step, _make(opt), 0, STEPS)
    if not np.array_equal(np.asarray(state.master),
                          np.asarray(golden.master)):
        raise SystemExit(f"{tag} resumed trajectory diverged from golden")
    print(f"{tag} resumed from quorum step {restored.step}, replay "
          "bitwise-identical: OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
