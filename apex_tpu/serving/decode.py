"""Donation-aware jitted prefill + decode steps over the paged cache.

The serving analog of ``optimizers/train_step.py``: each step is ONE
compiled program with the cache pools DONATED (``donate_argnums``), so
a decode step appends K/V in place — the pool never holds two copies,
and the hot loop allocates nothing. A model with recurrent layers
(docs/serving.md "Recurrent state") has its state pools donated with
them: each lane's ``state_slots`` entry names the slot its state is
read from and written back to, and a lane that starts a sequence (a
whole-prompt prefill, a chunk at position 0) starts from zeros whatever
its slot held. Such an update is NOT idempotent as the K/V append is:
no dispatch that has run may be replayed. The per-shape compile cache is an
eviction-free dict keyed on the bucketed shapes:

- decode: ``(batch_bucket, table_width)`` — the only dynamic shapes a
  decode dispatch has (a model with window layers carries a second
  table a lane, the tail its window layers gather through, and every
  key ends in that table's width too: docs/serving.md "Window
  layers");
- prefill: ``(batch_bucket, seq_bucket, table_width)``;
- prefill_chunk: ``(batch_bucket, chunk_bucket, table_width)`` — the
  chunk-resumable prefill (chunked prefill / prefix-cache resume),
  which attends the already-written context and appends the chunk.

Every NEW key is observed by the PR-6 compile tracker
(``telemetry.compiled.observe``) under ``fn="decode_step"`` /
``fn="prefill_step"`` / ``fn="prefill_chunk"`` and the compiling
dispatch runs inside a ``label(...)`` scope, so decode-shape churn
shows up as ``recompile`` events with a signature diff — and a
scheduler that buckets properly triggers ZERO recompile events after
warmup (tools/check_serving.sh pins it). Cache hits never reach the
tracker: the hot loop is one dict lookup.

Fused hot path (PAPERS.md "LLM Inference Acceleration via Efficient
Operation Fusion" — the prefill/decode analog of PR 1's fused
optimizer step): prefill runs embed -> L layers -> final norm -> LM
head -> last-token logit gather -> cache append as one program;
decode runs, per layer inside the layer scan, one gather of that
layer's context out of the pool (``ops/kv_gather.py``, straight into
the flash kernel's layout) -> the token's own K/V into its slot of it
-> single-query attention, then logits -> token selection -> cache
append, as one program. No program holds more than one layer's
context, and the pools go into the scan whole: read there, written
only by the append after it. Token selection is FUSED in-program too:
a per-lane temperature / top-k / top-p sampler draws from a
counter-based PRNG
key (``fold_in(PRNGKey(seed), emitted_token_index)`` — pure function
of the request seed and the token's sequence index, so a drain/resume
replay regenerates the identical stream), gated by ``lax.cond`` so an
all-greedy batch never pays the sort. ``temperature == 0`` lanes take
the greedy argmax — bitwise the pre-sampling behavior.

The host-device boundary of a dispatch is one array each way. In:
every small host argument of the call (tokens, lengths / starts /
positions, block tables, the window layers' tables, the four sampling
arrays) goes into ONE int32 numpy buffer whose layout is a function of
the program's key alone (:func:`packed_layout`), so it is one transfer
and a program warmed on zeros is the program traffic runs; the program
takes it apart by static slices and bit casts (float32 and uint32
arrive bit for bit). Out: ``StepOut.packed``, a (2, b) int32 array of
the token ids over the finite flags, so the engine reads both in one
fetch (:func:`host_tokens`) with no wait before it. The (b,) ids, the
(b,) flags and the (b, vocab) logits stay on ``StepOut`` for whoever
wants them. ``DecodeStep.transfers`` counts both sides.

Both steps are teacher-forcing-friendly: they return the raw last
logits next to the selected ids, so the parity suite replays a known
sequence through decode and compares against the full-sequence
forward (tests/test_serving.py).
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from apex_tpu.serving.kv_cache import (
    KVCache,
    KVCacheState,
    append_kv,
    append_kv_chunk,
    append_kv_prefill,
)


class StepOut(NamedTuple):
    """One prefill/decode dispatch's results (device arrays)."""

    logits: Any        # (batch, vocab) fp32 — the LAST real token's
    # (batch,) int32 — the selected next token: per-lane fused
    # temperature/top-k/top-p sample, or the greedy argmax for
    # temperature == 0 lanes (bitwise the pre-sampling behavior)
    next_token: Any
    cache: KVCacheState
    # (batch,) bool — every logit of the lane is finite. Computed
    # IN-JIT (one fused reduction over logits the program already
    # holds), so per-request fault isolation costs the host a (b,)
    # bool pull instead of the full (b, vocab) logits
    # (serving/resilience.py quarantine path). None on older callers.
    finite: Any = None
    # (2, batch) int32 — ``next_token`` over ``finite`` as 0 / 1: what
    # the engine reads, in one fetch. None from a stand-in step_fn.
    packed: Any = None


def host_tokens(out: StepOut) -> np.ndarray:
    """A dispatch's token ids over its finite flags, (2, batch) int32
    on the host: the one blocking read of a dispatch, of
    ``out.packed``. A stand-in step_fn's ``StepOut`` has none: its
    ``next_token`` and ``finite`` (every lane finite where None) are
    read as they always were."""
    if out.packed is not None:
        return np.asarray(out.packed)
    ids = np.asarray(out.next_token, np.int32)
    return np.stack([ids, np.ones_like(ids) if out.finite is None
                     else np.asarray(out.finite, np.int32)])


def greedy_sampling(b: int) -> Tuple[np.ndarray, ...]:
    """The all-greedy sampling arrays for a batch of ``b`` lanes —
    what every dispatch uses when the caller passes ``sampling=None``
    (temperature 0, no top-k, top-p 1, seed 0)."""
    return (np.zeros(b, np.float32), np.zeros(b, np.int32),
            np.ones(b, np.float32), np.zeros(b, np.uint32))


# the fields of a dispatch's one host argument that are not int32;
# they ride the int32 buffer as their bits (``ndarray.view`` on the
# host, ``lax.bitcast_convert_type`` in the program)
_FIELD_DTYPES = {"temps": np.float32, "top_ps": np.float32,
                 "seeds": np.uint32}
_LANE_FIELDS = {"decode_step": ("positions",),
                "prefill_step": ("lengths",),
                "prefill_chunk": ("starts", "lengths")}

Layout = Tuple[Tuple[str, Tuple[int, ...]], ...]


def packed_layout(fn: str, batch: int, table_width: int, seq: int = 1,
                  window_table_width: Optional[int] = None,
                  state_slots: bool = False) -> Layout:
    """``(name, shape)`` of every field of program ``fn``'s one host
    argument, in the order they lie in the buffer: a function of the
    program's key alone, the same with and without sampling arrays.
    ``state_slots``: the model has recurrent layers, and each lane
    names its state slot."""
    if fn not in _LANE_FIELDS:
        raise ValueError(f"unknown serving program {fn!r}")
    b = batch
    fields = [("tokens", (b,) if fn == "decode_step" else (b, seq))]
    fields += [(name, (b,)) for name in _LANE_FIELDS[fn]]
    fields.append(("tables", (b, table_width)))
    if window_table_width is not None:
        fields += [("window_tables", (b, window_table_width)),
                   ("window_first", (b,))]
    if state_slots:
        fields.append(("state_slots", (b,)))
    fields += [(name, (b,))
               for name in ("temps", "top_ks", "top_ps", "seeds")]
    return tuple(fields)


def packed_size(layout: Layout) -> int:
    """The buffer's length, in int32 words."""
    return sum(math.prod(shape) for _, shape in layout)


def pack(layout: Layout, fields: Dict[str, Any]) -> np.ndarray:
    """The one int32 buffer of a dispatch, from its host arrays by
    name. Numpy only: a ``jnp`` operator here would be a transfer, and
    a jit, of its own."""
    parts = []
    for name, shape in layout:
        a = np.asarray(fields[name], _FIELD_DTYPES.get(name, np.int32))
        if a.shape != shape:
            raise ValueError(f"{name}: shape {a.shape}, the program's "
                             f"key says {shape}")
        parts.append(a.view(np.int32).ravel())
    return np.concatenate(parts)


def unpack(packed, layout: Layout) -> Dict[str, Any]:
    """Inside the program: the fields of ``packed`` by name, by static
    slices, reshapes and bit casts."""
    import jax

    fields, at = {}, 0
    for name, shape in layout:
        n = math.prod(shape)
        x = packed[at:at + n].reshape(shape)
        if name in _FIELD_DTYPES:
            x = jax.lax.bitcast_convert_type(x, _FIELD_DTYPES[name])
        fields[name] = x
        at += n
    return fields


class DecodeStep:
    """Compiled prefill + decode dispatchers for one (model, cache).

    Build via :func:`make_decode_step`. The cache state passed to
    either method is DONATED — rebind it to ``out.cache``; the buffers
    you passed in are dead after the call.
    """

    def __init__(self, model, cache: KVCache):
        import jax
        import jax.numpy as jnp

        self.model = model
        self.cache = cache
        # key -> (layout of its host argument, its jitted program)
        self._compiled: Dict[Tuple, Tuple[Layout, Any]] = {}
        # what crosses the host-device boundary: the host arrays
        # handed to a program and the arrays it makes for the host to
        # read, counted where a dispatch makes them (one of each)
        self.transfers = {"dispatches": 0, "host_arrays_in": 0,
                          "host_arrays_out": 0}
        cfg = model.config
        max_pos = cfg.max_seq_len - 1
        # a model with recurrent layers: every dispatch names its
        # lanes' state slots (read off the cache, like its pool)
        self.recurrent = bool(cache.state_slots)

        def apply(params, state, tokens, *, state_slots, lengths, fresh,
                  **kw):
            """The model over the cache: ``(logits, new, state)``,
            ``new`` this call's rows, one array a pool.
            Where it has recurrent layers it is handed its lanes'
            slots of the state pools and gives the pools back."""
            if state_slots is None:
                logits, kv = model.apply(params, tokens, return_kv=True,
                                         **kw)
                return logits, kv, state
            logits, kv, pools = model.apply(
                params, tokens, return_kv=True,
                state_ctx=(state.state, state_slots, lengths, fresh), **kw)
            return logits, kv, state._replace(state=pools)

        def tail(tables, first):
            # the window layers' tables, where the model has such
            # layers: one more element of the kv_ctx hook
            return () if tables is None else ((tables, first),)

        def select_token(out, sampling, fold_pos):
            """Fused in-program token selection over the (b, vocab)
            fp32 logits ``out``: greedy argmax for temperature-0
            lanes (bitwise the pre-sampling path), a per-lane
            temperature/top-k/top-p gumbel-max draw otherwise. The
            PRNG key is counter-based — ``fold_in(PRNGKey(seed),
            fold_pos)`` with ``fold_pos`` the emitted token's global
            sequence index — so replaying a prefix regenerates the
            identical stream (the drain/resume contract)."""
            temps, top_ks, top_ps, seeds = sampling
            greedy = jnp.argmax(out, axis=-1).astype(jnp.int32)

            def sample(_):
                b, v = out.shape
                t = jnp.where(temps > 0, temps, 1.0).astype(jnp.float32)
                scaled = out.astype(jnp.float32) / t[:, None]
                sdesc = -jnp.sort(-scaled, axis=-1)     # descending
                kk = jnp.where(top_ks > 0, jnp.clip(top_ks, 1, v),
                               v).astype(jnp.int32)
                kth = jnp.take_along_axis(sdesc, (kk - 1)[:, None],
                                          axis=1)
                probs = jax.nn.softmax(sdesc, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                # nucleus: keep the smallest prefix whose mass >= p
                # (entry i survives iff the mass BEFORE it is < p)
                keep = jnp.concatenate(
                    [jnp.ones((b, 1), bool),
                     cum[:, :-1] < top_ps[:, None]], axis=1)
                n_keep = jnp.sum(keep, axis=-1).astype(jnp.int32)
                pth = jnp.take_along_axis(sdesc, (n_keep - 1)[:, None],
                                          axis=1)
                thresh = jnp.maximum(kth, pth)
                masked = jnp.where(scaled >= thresh, scaled, -jnp.inf)

                def one(seed, pos, row):
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(seed), pos)
                    g = jax.random.gumbel(key, row.shape, jnp.float32)
                    return jnp.argmax(row + g)

                drawn = jax.vmap(one)(seeds, fold_pos,
                                      masked).astype(jnp.int32)
                return jnp.where(temps > 0, drawn, greedy)

            # an all-greedy batch never pays the sort/softmax/cumsum
            return jax.lax.cond(jnp.any(temps > 0), sample,
                                lambda _: greedy, None)

        def step_out(out, nxt, state):
            finite = jnp.all(jnp.isfinite(out), axis=-1)
            return StepOut(out, nxt, state, finite,
                           jnp.stack([nxt, finite.astype(jnp.int32)]))

        def prefill_fn(params, state, *, tokens, lengths, tables, temps,
                       top_ks, top_ps, seeds, state_slots=None):
            b, s = tokens.shape
            with jax.named_scope("embed"):
                fresh = jnp.ones((b,), bool)
            logits, new, state = apply(
                params, state, tokens, state_slots=state_slots,
                lengths=lengths, fresh=fresh)
            with jax.named_scope("cache"):
                state = append_kv_prefill(state, new, tables, lengths)
            with jax.named_scope("head"):
                last = jnp.clip(lengths - 1, 0, s - 1)
                out = logits[last, jnp.arange(b)]      # (b, vocab)
                # the emitted token lands at sequence index == prompt len
                nxt = select_token(out, (temps, top_ks, top_ps, seeds),
                                   lengths)
                return step_out(out, nxt, state)

        def prefill_chunk_fn(params, state, *, tokens, starts, lengths,
                             tables, temps, top_ks, top_ps, seeds,
                             window_tables=None, window_first=None,
                             state_slots=None):
            b, s = tokens.shape
            # the pools are read BEFORE the chunk's writes: each layer
            # gathers its own context, every previously-written
            # position (< starts); the chunk's own K/V rides kv_new
            # inside the attention
            with jax.named_scope("embed"):
                pos = jnp.clip(
                    starts[:, None]
                    + jnp.arange(s, dtype=jnp.int32)[None, :], 0, max_pos)
                fresh = starts == 0
            logits, new, state = apply(
                params, state, tokens, state_slots=state_slots,
                lengths=lengths, fresh=fresh, positions=pos,
                kv_ctx=(state.pools, tables, starts,
                        *tail(window_tables, window_first)))
            with jax.named_scope("cache"):
                state = append_kv_chunk(state, new, tables, starts, lengths)
            with jax.named_scope("head"):
                last = jnp.clip(lengths - 1, 0, s - 1)
                out = logits[last, jnp.arange(b)]      # (b, vocab)
                # only meaningful on a prompt-completing chunk: the
                # emitted token's index is starts + chunk length
                nxt = select_token(out, (temps, top_ks, top_ps, seeds),
                                   starts + lengths)
                return step_out(out, nxt, state)

        def decode_fn(params, state, *, tokens, positions, tables, temps,
                      top_ks, top_ps, seeds, window_tables=None,
                      window_first=None, state_slots=None):
            with jax.named_scope("embed"):
                pos2 = jnp.clip(positions, 0, max_pos)[:, None]   # (b, 1)
                tokens = tokens[:, None]
                fresh = jnp.zeros(positions.shape, bool)
            # each layer gathers its own context from the pools and
            # attends with the token's K/V in slot positions[b] of it:
            # the slot append_kv writes below, which the table covers
            logits, new, state = apply(
                params, state, tokens, state_slots=state_slots,
                lengths=None, fresh=fresh, positions=pos2,
                kv_ctx=(state.pools, tables, positions,
                        *tail(window_tables, window_first)))
            with jax.named_scope("cache"):
                state = append_kv(state, tuple(t[:, :, :, 0] for t in new),
                                  tables, positions)
            with jax.named_scope("head"):
                out = logits[0]                        # (b, vocab)
                # the emitted token lands at positions + 1
                nxt = select_token(out, (temps, top_ks, top_ps, seeds),
                                   positions + 1)
                return step_out(out, nxt, state)

        # what each program computes from its fields, by name
        bodies = self._bodies = {"prefill_step": prefill_fn,
                                 "prefill_chunk": prefill_chunk_fn,
                                 "decode_step": decode_fn}

        def program(fn: str, layout: Layout):
            """The jitted program of one key: ``(params, state,
            packed)``, cache state donated (argnums 1: appends run in
            place)."""
            body = bodies[fn]

            def run(params, state, packed):
                # every operation of a program lies under one part of
                # the model (telemetry.compiled.PARTS): taking the host
                # argument apart is the cache's table arithmetic
                with jax.named_scope("cache"):
                    fields = unpack(packed, layout)
                return body(params, state, **fields)

            # the program's name in a device trace (jit_decode_fn,
            # jit_prefill_fn, ...): what a trace's readers find it by
            run.__name__ = body.__name__
            return jax.jit(run, donate_argnums=(1,))

        self._program = program

    # -- compile-plane bookkeeping ------------------------------------------

    def _signature(self, fn: str, key: Tuple) -> Dict[str, Any]:
        cfg = self.model.config
        sig: Dict[str, Any] = {"fn": fn}
        if fn in ("prefill_step", "prefill_chunk"):
            sig.update(batch=key[1], seq=key[2], table_width=key[3])
            rest = key[4:]
        else:
            sig.update(batch=key[1], table_width=key[2])
            rest = key[3:]
        if rest:
            sig.update(window_table_width=rest[0])
        sig.update(block_size=self.cache.block_size,
                   kv_heads=self.cache.kv_heads,
                   head_dim=self.cache.head_dim,
                   num_layers=cfg.num_layers)
        if self.recurrent:
            sig.update(state_slots=self.cache.state_slots)
        return sig

    def _dispatch(self, fn: str, params, state, fields: Dict[str, Any],
                  sampling, window=None, slots=None) -> StepOut:
        """Pack ``fields`` (host arrays by name) with the sampling and
        window arrays into the program's one argument and run it. Hits
        are one dict lookup and never reach the compile tracker (the
        train-step ``_track`` discipline)."""
        tokens = fields["tokens"] = np.asarray(fields["tokens"], np.int32)
        tables = fields["tables"] = np.asarray(fields["tables"], np.int32)
        b, width = tokens.shape[0], tables.shape[1]
        seq = tokens.shape[1:]                  # () for a decode
        widths: Tuple[int, ...] = ()
        if window is not None:
            win_tables, fields["window_first"] = window
            fields["window_tables"] = np.asarray(win_tables, np.int32)
            widths = (fields["window_tables"].shape[1],)
        if self.recurrent:
            if slots is None:
                raise ValueError(
                    f"{fn}: the model has recurrent layers, and the "
                    "dispatch names no state slots (KVCache.slot_array)")
            fields["state_slots"] = slots
        (fields["temps"], fields["top_ks"], fields["top_ps"],
         fields["seeds"]) = (greedy_sampling(b) if sampling is None
                             else sampling)
        key = (fn, b, *seq, width, *widths)
        entry = self._compiled.get(key)
        new = entry is None
        if new:
            layout = packed_layout(fn, b, width, *(seq or (1,)),
                                   *(widths or (None,)), self.recurrent)
            entry = (layout, self._program(fn, layout))
        layout, program = entry
        packed = pack(layout, fields)
        self.transfers["dispatches"] += 1
        self.transfers["host_arrays_in"] += 1       # packed
        self.transfers["host_arrays_out"] += 1      # StepOut.packed
        if new:
            self._compiled[key] = entry
            from apex_tpu.telemetry import compiled as _compiled

            signature = self._signature(fn, key)
            _compiled.observe(fn, signature)
            _compiled.register_program(
                "jit_" + program.__name__, signature, program,
                (params, state, packed))
            from apex_tpu.mesh import mesh as _gspmd_mesh

            if _gspmd_mesh.mesh_initialized() \
                    and _gspmd_mesh.mesh_size() > 1:
                # mesh-armed serving: introspect+publish this key's
                # compiled shardings (sharding_devices{fn=}) BEFORE
                # the donating dispatch consumes the args — one extra
                # compile per NEW key, only when a real mesh is live
                from apex_tpu.telemetry import sharding as _sharding

                _sharding.publish_shardings(_sharding.jitted_shardings(
                    program, params, state, packed, fn=fn))
            with _compiled.label(fn):
                return program(params, state, packed)
        return program(params, state, packed)

    def compile_keys(self) -> Dict[str, int]:
        """Distinct compiled shapes per step kind (the bench/smoke
        assertion surface: the expected decode-bucket compile count)."""
        out: Dict[str, int] = {"prefill_step": 0, "prefill_chunk": 0,
                               "decode_step": 0}
        for key in self._compiled:
            out[key[0]] += 1
        return out

    def lower(self, fn: str, params, state: KVCacheState, batch: int,
              table_width: int, seq: int = 1,
              window_table_width: Optional[int] = None):
        """``jax.jit(...).lower`` passthrough for one bucketed program
        (``TrainStep.lower``'s sibling): ``fn`` is ``"decode_step"``,
        ``"prefill_step"`` or ``"prefill_chunk"`` (the last two take
        the ``seq`` bucket). Built from shapes alone — ``params`` and
        ``state`` may be ``jax.ShapeDtypeStruct`` trees — so a
        program's memory analysis and text can be had without a
        dispatch. ``window_table_width`` is the second table's, for a
        model with window layers."""
        import jax

        layout = packed_layout(fn, batch, table_width, seq,
                               window_table_width, self.recurrent)
        return self._program(fn, layout).lower(
            params, state,
            jax.ShapeDtypeStruct((packed_size(layout),), np.int32))

    # -- dispatchers ---------------------------------------------------------

    def prefill(self, params, state: KVCacheState, tokens, lengths,
                tables, sampling=None, slots=None) -> StepOut:
        """Run the full (right-padded) prompts, write their K/V into
        the pool, and return the LAST real token's logits — the first
        generated token's distribution — in one program.

        ``tokens`` (b, s) int32; ``lengths`` (b,) real prompt lengths;
        ``tables`` (b, w) block tables (trash-padded); ``sampling``
        optional ``(temps, top_ks, top_ps, seeds)`` per-lane arrays
        (None = all-greedy). Dummy batch rows use length 0 and an
        all-trash table. ``slots`` (b,) are the lanes' state slots
        (``KVCache.slot_array``; the trash slot for a dummy row) for a
        model with recurrent layers, here and in the two below: a
        prompt prefilled here starts from zeros whatever its slot held.
        """
        return self._dispatch(
            "prefill_step", params, state,
            {"tokens": tokens, "lengths": lengths, "tables": tables},
            sampling, slots=slots)

    def prefill_chunk(self, params, state: KVCacheState, tokens,
                      starts, lengths, tables,
                      sampling=None, window=None, slots=None) -> StepOut:
        """Resume prefill with one CHUNK per sequence: row ``i`` of
        lane ``b`` is the prompt token at global position
        ``starts[b] + i`` (``lengths[b]`` real rows, the rest pad).
        The chunk attends the already-written cache prefix (gathered
        in-program) plus itself causally, writes its K/V at the
        offset positions, and emits the last real row's logits — the
        first-token distribution when the chunk completes the prompt.
        One program, cache donated; the chunked-prefill hot path
        (docs/serving.md "Chunked prefill"). ``window`` is ``(tables,
        first)`` of ``KVCache.window_table_array`` for a model with
        window layers (here and in :meth:`decode`). A recurrent
        model's pad rows (``>= lengths[b]``) do not advance its state,
        and a chunk that starts at position 0 starts from zeros.
        """
        return self._dispatch(
            "prefill_chunk", params, state,
            {"tokens": tokens, "starts": starts, "lengths": lengths,
             "tables": tables}, sampling, window, slots)

    def decode(self, params, state: KVCacheState, tokens, positions,
               tables, sampling=None, window=None, slots=None) -> StepOut:
        """One token per sequence: gather each sequence's cache view,
        attend (single query, per-sequence length via the mask), emit
        logits + the selected next token, and append the new K/V at
        ``positions`` — one program, cache donated.

        ``tokens`` (b,) int32 current tokens; ``positions`` (b,) their
        0-based positions (== the cached prefix length); ``sampling``
        optional per-lane ``(temps, top_ks, top_ps, seeds)`` (None =
        all-greedy). Dummy batch rows use position 0 and an all-trash
        table.
        """
        return self._dispatch(
            "decode_step", params, state,
            {"tokens": tokens, "positions": positions, "tables": tables},
            sampling, window, slots)


def make_decode_step(model, cache: KVCache) -> DecodeStep:
    """Build the compiled serving steps for ``model`` (a
    :class:`~apex_tpu.models.gpt.GPTModel`, a
    :class:`~apex_tpu.models.decoder.PatternDecoder`) over ``cache``.

    The returned :class:`DecodeStep` donates the cache state on every
    dispatch and keeps an eviction-free per-shape compile cache
    observed by the compile tracker (module docstring)."""
    return DecodeStep(model, cache)


__all__ = ["DecodeStep", "StepOut", "greedy_sampling", "host_tokens",
           "make_decode_step", "pack", "packed_layout", "packed_size",
           "unpack"]
