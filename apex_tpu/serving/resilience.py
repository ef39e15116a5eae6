"""Serving-tier resilience: drain snapshots, resume, and weight hot-swap.

PR 8's serving engine stops degrading at admission control: before this
module a decode-step exception killed every in-flight request, a
SIGTERM dropped the whole queue, and new weights meant a restart. This
module (plus the scheduler/decode integration in
``serving/scheduler.py``) is the serving analog of the training-side
resilience stack — the north star's "heavy traffic from millions of
users" must degrade per-REQUEST, not per-process:

- **deadlines** — ``Request.deadline_ms`` is a TTL from submission;
  expired requests (queued or in-flight) are reaped at the top of every
  engine step, BEFORE admission and decode, with outcome
  ``deadline_exceeded`` (scheduler integration; counter
  ``serving_deadline_exceeded``).
- **quarantine** — a decode dispatch that raises is retried by binary
  split (the watchdog's localization idiom lifted to the batch axis):
  halves that succeed keep their tokens, the offending sequence(s)
  bottom out as singletons and finish with outcome ``error`` while the
  engine keeps serving. Nonfinite logits localize for free — the
  decode program's in-jit per-lane finite flag
  (:class:`~apex_tpu.serving.decode.StepOut`) names the poisoned
  lane(s) directly. Both paths fire the ``serving_quarantine`` flight
  trigger (replacing the old fail-everything ``serving_request_error``
  decode path).
- **drain snapshots** — when the scheduler's
  :class:`~apex_tpu.resilience.guard.PreemptionHandler` flags, the
  engine stops admitting and :func:`save_snapshot` persists every
  queued + in-flight request (prompt, generated-so-far tokens,
  deadline) as one sha256-manifested JSON under the checkpoint
  tmp→fsync→rename discipline
  (:func:`~apex_tpu.resilience.checkpoint.atomic_write_files`). A
  fresh engine resumes via :func:`resume_requests` — each in-flight
  prefix (prompt + generated) replays through the existing prefill
  path, so the resumed token stream is identical to the uninterrupted
  run — and :func:`merge_results` stitches the replayed prefixes back
  onto the resumed results.
- **weight hot-swap** — :func:`swap_weights` validates new params
  against the serving model's space signature (tree paths, shapes,
  dtypes; optionally a per-leaf ``guard.state_fingerprint``-style
  uint32 manifest from an elastic checkpoint), stages them on the
  engine, and the scheduler installs them at the next step boundary —
  between decode dispatches, so no request is dropped — emitting
  ``serving_weight_swap`` with old/new sha256 digests. A
  shape-mismatched swap raises :class:`WeightSwapError` carrying the
  structured per-leaf mismatch list and never touches the engine.

Fault clauses (resilience/faults.py, docs/resilience.md grammar):
``decode_nonfinite=<steps>`` (+ ``decode_nonfinite_lane=<i>``) poisons
one lane's cached K/V with NaN so its logits go nonfinite through the
real attention path; ``serving_snapshot_corrupt=<idx>`` truncates a
finalized drain snapshot; ``weight_swap_mismatch=<idx>`` forces the
swap validator to reject. ``tools/check_serving.sh`` drives the chaos
drill: 200 requests + ``decode_nonfinite`` + a mid-run SIGTERM must
quarantine only the poisoned sequence, snapshot the rest, resume, and
land >= 90% of the fault-free goodput with zero requests silently
dropped.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from apex_tpu.resilience import faults
from apex_tpu.resilience.checkpoint import atomic_write_files

SNAPSHOT_FORMAT = 1
SNAPSHOT_FILE = "snapshot.json"
SNAPSHOT_MANIFEST = "manifest.json"
_SNAP_RE = re.compile(r"^serving_(\d{12})$")


class SnapshotError(RuntimeError):
    """Unusable serving snapshot (missing, corrupt, or wrong format)."""


class WeightSwapError(RuntimeError):
    """A rejected weight hot-swap. ``mismatches`` is the structured
    per-leaf diff: ``[{"path", "expected", "got"}, ...]`` — shapes/
    dtypes/tree paths that disagree with the serving model's current
    signature (or the fingerprint row that failed)."""

    def __init__(self, msg: str, mismatches: List[Dict[str, Any]]):
        super().__init__(msg)
        self.mismatches = list(mismatches)


# ---------------------------------------------------------------------------
# Drain snapshots
# ---------------------------------------------------------------------------


def snapshot_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"serving_{int(step):012d}")


def save_snapshot(batcher, directory: str, *, step: int,
                  reason: str = "preemption") -> str:
    """Persist every queued + in-flight request of ``batcher`` as one
    atomic snapshot directory; returns the final path.

    The payload is JSON (request ids must be JSON-serializable —
    anything else cannot survive a process death anyway) with a sha256
    manifest; the write goes through the checkpoint discipline
    (tmp→fsync→rename), so a crash mid-drain leaves either nothing or
    a snapshot that verifies. The ``serving_snapshot_corrupt=<idx>``
    fault clause truncates the FINALIZED payload — exactly what
    :func:`latest_snapshot` must refuse.
    """
    entries = batcher._snapshot_entries()
    payload = {
        "format": SNAPSHOT_FORMAT,
        "step": int(step),
        "reason": str(reason),
        "utc": time.strftime("%Y%m%dT%H%M%SZ", time.gmtime()),
        "requests": entries,
    }
    # the armed goodput ledger rides the drain snapshot the same way
    # it rides training checkpoints — a killed-and-resumed serving
    # process keeps its run-level attribution
    from apex_tpu.telemetry import goodput as _goodput

    led = _goodput.get_ledger()
    if led is not None:
        payload["goodput"] = led.pack()
    data = json.dumps(payload, sort_keys=True).encode()
    manifest = {
        "format": SNAPSHOT_FORMAT,
        "step": int(step),
        "payload_bytes": len(data),
        "sha256": hashlib.sha256(data).hexdigest(),
        "n_requests": len(entries),
    }
    os.makedirs(directory, exist_ok=True)
    final = snapshot_path(directory, step)
    faults.check("serving_snapshot")
    atomic_write_files(final, {
        SNAPSHOT_FILE: data,
        SNAPSHOT_MANIFEST: json.dumps(manifest, indent=1,
                                      sort_keys=True).encode(),
    })
    idx = batcher._snapshot_count
    batcher._snapshot_count += 1
    if faults.should_snapshot_corrupt(idx):
        # simulated on-disk corruption of the FINALIZED snapshot
        with open(os.path.join(final, SNAPSHOT_FILE), "r+b") as f:
            f.truncate(max(1, len(data) // 2))
    reg = batcher._registry
    reg.counter("serving_snapshots",
                "serving drain snapshots committed").inc()
    reg.event("serving_snapshot_saved", path=final, step=int(step),
              n_requests=len(entries), reason=str(reason))
    return final


def validate_snapshot(path: str) -> Tuple[bool, str]:
    """(ok, reason): re-hash the payload against the manifest, so
    truncation/corruption is detected before a byte is parsed."""
    try:
        with open(os.path.join(path, SNAPSHOT_MANIFEST)) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        return False, f"manifest unreadable: {type(e).__name__}"
    if manifest.get("format") != SNAPSHOT_FORMAT:
        return False, f"unsupported format {manifest.get('format')!r}"
    ppath = os.path.join(path, SNAPSHOT_FILE)
    try:
        size = os.path.getsize(ppath)
    except OSError:
        return False, "payload missing"
    if size != manifest.get("payload_bytes"):
        return False, (f"payload truncated: {size} bytes, manifest says "
                       f"{manifest.get('payload_bytes')}")
    h = hashlib.sha256()
    try:
        with open(ppath, "rb") as f:
            h.update(f.read())
    except OSError as e:
        return False, f"payload unreadable: {type(e).__name__}"
    if h.hexdigest() != manifest.get("sha256"):
        return False, "sha256 mismatch"
    return True, ""


def load_snapshot(path: str) -> Dict[str, Any]:
    """Parse a snapshot that :func:`validate_snapshot` accepts; raises
    :class:`SnapshotError` otherwise — a rotten snapshot must never be
    resumed."""
    ok, reason = validate_snapshot(path)
    if not ok:
        raise SnapshotError(f"{path}: {reason}")
    with open(os.path.join(path, SNAPSHOT_FILE)) as f:
        return json.load(f)


def latest_snapshot(directory: str, *,
                    record_events: bool = True) -> Optional[str]:
    """Newest snapshot under ``directory`` that verifies, scanning
    newest -> oldest; corrupt ones are reported (counter
    ``serving_snapshot_corrupt_skipped`` + event) and skipped — the
    ``latest_valid()`` contract for the serving tier."""
    try:
        names = os.listdir(directory)
    except OSError:
        return None
    steps = sorted(int(m.group(1)) for m in map(_SNAP_RE.match, names)
                   if m)
    for step in reversed(steps):
        path = snapshot_path(directory, step)
        ok, reason = validate_snapshot(path)
        if ok:
            return path
        if record_events:
            from apex_tpu.telemetry import metrics as _metrics

            reg = _metrics.registry()
            reg.counter("serving_snapshot_corrupt_skipped",
                        "corrupt serving snapshots skipped by "
                        "latest_snapshot").inc()
            reg.event("corrupt_serving_snapshot", path=path, step=step,
                      reason=reason)
    return None


def resume_requests(snapshot: Dict[str, Any]):
    """Rebuild the requests a drained engine owed from a snapshot
    payload; returns ``(requests, prior)``.

    In-flight entries resume through the EXISTING prefill path: the
    replay prompt is ``prompt + generated`` (reconstructing the cache
    the dead engine held, bit-for-bit the same K/V the prefill scatter
    writes) and ``max_new_tokens`` shrinks by what was already
    generated, so the resumed engine's first emitted token is exactly
    the next one the uninterrupted run would have produced — for
    SAMPLED streams too: the per-request RNG is counter-based
    (``fold_in(seed, token index)``, serving/decode.py), and the
    snapshot carries the sampling knobs + seed, so the resumed
    engine's draws continue the stream token for token. ``prior``
    maps request id -> the already-generated prefix;
    :func:`merge_results` folds it back so callers see full token
    streams.

    The request plane rides along: each entry's persisted ``trace_id``
    is handed back on the rebuilt request with ``resumed_from`` naming
    the snapshot, so a traced resumed engine CONTINUES the same trace
    (serving/tracing.py) instead of minting a fresh one.
    """
    from apex_tpu.serving.scheduler import Request

    if snapshot.get("format") != SNAPSHOT_FORMAT:
        raise SnapshotError(
            f"unsupported snapshot format {snapshot.get('format')!r}")
    # restart survival: fold the dead engine's goodput ledger (when the
    # snapshot carries one and this process's ledger is armed) into the
    # resumed process's cumulative attribution
    from apex_tpu.telemetry import goodput as _goodput

    _goodput.note_restored(snapshot)
    origin = f"serving_{int(snapshot.get('step', 0)):012d}"
    requests: List[Request] = []
    prior: Dict[Any, List[int]] = {}
    for e in snapshot.get("requests", []):
        generated = [int(t) for t in e.get("generated", [])]
        prompt = [int(t) for t in e["prompt"]] + generated
        remaining = int(e["max_new_tokens"]) - len(generated)
        if remaining < 1:          # finished at the snapshot boundary
            continue
        requests.append(Request(
            id=e["id"], prompt=prompt, max_new_tokens=remaining,
            eos_id=e.get("eos_id"), deadline_ms=e.get("deadline_ms"),
            temperature=float(e.get("temperature", 0.0)),
            top_k=int(e.get("top_k", 0)),
            top_p=float(e.get("top_p", 1.0)),
            seed=int(e.get("seed", 0)),
            trace_id=e.get("trace_id"),
            resumed_from=origin))
        prior[e["id"]] = generated
    return requests, prior


def merge_results(results, prior: Dict[Any, List[int]]):
    """Stitch the snapshotted prefixes back onto resumed results: each
    result's ``tokens`` becomes ``prior[id] + tokens`` (ids absent from
    ``prior`` pass through), so the caller-visible stream matches the
    uninterrupted run token for token."""
    import dataclasses

    out = []
    for r in results:
        pre = prior.get(r.id)
        if pre:
            r = dataclasses.replace(r, tokens=list(pre) + list(r.tokens))
        out.append(r)
    return out


# ---------------------------------------------------------------------------
# Nonfinite injection helper (the decode_nonfinite drill site)
# ---------------------------------------------------------------------------


def poison_lane_kv(state, cache, seq_id, position: int):
    """Overwrite one cached K/V row of ``seq_id`` at ``position`` with
    NaN (host-side, between dispatches — the serving analog of
    ``faults.poison_grads``). The next decode of that lane attends the
    poisoned row, so its logits come out nonfinite through the REAL
    attention path and the in-jit finite flag localizes it."""
    import jax.numpy as jnp

    table = cache.table(seq_id)
    bs = cache.block_size
    blk = table[int(position) // bs]
    slot = int(position) % bs
    return state._replace(pools=tuple(
        pool.at[:, blk, slot].set(jnp.nan) for pool in state.pools))


# ---------------------------------------------------------------------------
# Live weight hot-swap
# ---------------------------------------------------------------------------


def params_signature(params) -> List[Tuple[str, Tuple[int, ...], str]]:
    """The model's space signature: ``(path, shape, dtype)`` per leaf
    in tree-flatten order — what a hot-swapped replacement must match
    exactly (same tree, same shapes, same dtypes; values free)."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    return [(jax.tree_util.keystr(path), tuple(np.shape(leaf)),
             str(np.asarray(leaf).dtype)) for path, leaf in leaves]


def params_digest(params) -> str:
    """sha256 over every leaf's path, shape, dtype, and raw bytes in
    tree-flatten order — the weight identity ``serving_weight_swap``
    events carry (two param sets with the same digest serve the same
    distribution)."""
    import jax

    h = hashlib.sha256()
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in leaves:
        arr = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def params_fingerprint(params) -> np.ndarray:
    """Per-leaf bitwise uint32 checksums of ``params`` in tree-flatten
    order (each leaf's words reinterpreted as uint32 and summed mod
    2^32 — the ``guard.state_fingerprint`` reduction applied to a raw
    param tree), so a swap can be verified against the fingerprint
    manifest an elastic checkpoint recorded for the same leaf order."""
    import jax

    sums = []
    for leaf in jax.tree_util.tree_leaves(params):
        raw = np.ascontiguousarray(np.asarray(leaf)).view(np.uint8)
        pad = (-raw.size) % 4
        if pad:
            raw = np.concatenate([raw, np.zeros(pad, np.uint8)])
        words = raw.view(np.uint32).astype(np.uint64)
        sums.append(int(words.sum() % (1 << 32)))
    return np.asarray(sums, np.uint32)


def swap_weights(batcher, new_params, *,
                 expect_fingerprint=None) -> Dict[str, Any]:
    """Validate and stage ``new_params`` on a running engine; the
    scheduler installs them at its next step boundary — between decode
    dispatches — so no in-flight request is dropped (their cached K/V
    from the old weights is retained; generation continues under the
    new ones). Returns ``{"old_digest", "new_digest", "step"}`` where
    ``step`` is the engine step that will serve the swap.

    Rejections are structured and leave the engine untouched: a tree/
    shape/dtype mismatch against :func:`params_signature` (or an
    ``expect_fingerprint`` row disagreement, when the caller passes the
    per-leaf uint32 manifest a checkpoint recorded) raises
    :class:`WeightSwapError` listing every offending leaf, increments
    ``serving_weight_swap_rejected``, and dumps a
    ``serving_weight_swap`` flight bundle naming the mismatches. The
    ``weight_swap_mismatch=<idx>`` fault clause forces this path.
    """
    from apex_tpu.telemetry import flight as _flight

    reg = batcher._registry
    idx = batcher._swap_count
    batcher._swap_count += 1
    old_sig = params_signature(batcher.params)
    new_sig = params_signature(new_params)
    mismatches: List[Dict[str, Any]] = []
    if faults.should_weight_swap_mismatch(idx):
        mismatches.append({"path": "<injected>",
                           "expected": "matching signature",
                           "got": "weight_swap_mismatch fault"})
    old_by_path = dict((p, (s, d)) for p, s, d in old_sig)
    new_by_path = dict((p, (s, d)) for p, s, d in new_sig)
    for p, want in old_by_path.items():
        got = new_by_path.get(p)
        if got is None:
            mismatches.append({"path": p, "expected": list(want),
                               "got": "missing"})
        elif got != want:
            mismatches.append({"path": p, "expected": list(want),
                               "got": list(got)})
    for p in new_by_path:
        if p not in old_by_path:
            mismatches.append({"path": p, "expected": "absent",
                               "got": list(new_by_path[p])})
    if not mismatches and expect_fingerprint is not None:
        want = np.asarray(expect_fingerprint, np.uint32).reshape(-1)
        got = params_fingerprint(new_params)
        if want.shape != got.shape or not np.array_equal(want, got):
            bad = ([int(i) for i in np.nonzero(want != got)[0]]
                   if want.shape == got.shape else "shape")
            mismatches.append({"path": f"<fingerprint leaves {bad}>",
                               "expected": "manifest checksums",
                               "got": "different bits"})
    if mismatches:
        err = WeightSwapError(
            f"weight swap rejected: {len(mismatches)} leaf signature "
            f"mismatch(es), first at {mismatches[0]['path']!r}",
            mismatches)
        reg.counter("serving_weight_swap_rejected",
                    "hot-swaps refused by signature validation").inc()
        reg.event("serving_weight_swap_rejected",
                  n_mismatches=len(mismatches),
                  first=str(mismatches[0]["path"]))
        _flight.notify("serving_weight_swap", error=err, fleet=False,
                       extra={"rejected": True,
                              "mismatches": mismatches[:16]})
        raise err
    info = {"old_digest": params_digest(batcher.params),
            "new_digest": params_digest(new_params),
            "step": batcher.step_idx}
    batcher._stage_params(new_params, info)
    return info


__all__ = [
    "SNAPSHOT_FILE",
    "SNAPSHOT_FORMAT",
    "SNAPSHOT_MANIFEST",
    "SnapshotError",
    "WeightSwapError",
    "latest_snapshot",
    "load_snapshot",
    "merge_results",
    "params_digest",
    "params_fingerprint",
    "params_signature",
    "poison_lane_kv",
    "resume_requests",
    "save_snapshot",
    "snapshot_path",
    "swap_weights",
    "validate_snapshot",
]
