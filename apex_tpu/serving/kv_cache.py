"""Paged KV cache: fixed-size blocks over one preallocated pool.

The serving tier's memory subsystem (ROADMAP item 1; the vLLM
PagedAttention layout re-expressed for this stack): instead of one
contiguous ``(batch, max_seq_len)`` KV buffer per sequence — whose
reallocation/copy on every growth step is exactly the churn the
donation-aware train step was built to kill — every layer's K and V
live in ONE preallocated pool of fixed-size blocks,

    pool: (num_layers, num_blocks, block_size, kv_heads, head_dim)

and each sequence owns an ordered *block table* (a list of pool block
indices). Appending a token writes one ``(kv_heads, head_dim)`` row at
``(table[pos // block_size], pos % block_size)``; reading gathers a
layer's blocks through the table into a contiguous ``(kv_heads,
padded_len, head_dim)`` view for attention. Neither path ever reallocates the pool — the device
arrays are created once and donated through every decode step.

GQA pays GQA-sized blocks: the pool is sized from the model's
``kv_heads`` (``GPTConfig.kv_heads``), not ``num_heads``, so a 4x
grouped-query model holds 4x the sequences in the same HBM.

Block 0 is the **trash block**: writes from padded batch slots land
there (their tables name nothing else), and unallocated block-table
entries point at it so a short table gathers garbage that the
attention mask then drops. No real sequence is ever given block 0.

The allocator is host-side Python (the scheduler's admission control
runs on the host between steps). The jitted programs write the pool
through :func:`append_kv` / :func:`append_kv_prefill` /
:func:`append_kv_chunk` and read it per layer, inside the model's
layer scan, through ``ops/kv_gather.py`` (the ``kv_ctx`` hook of
``models/gpt.py``); :func:`gather_kv` is that read for all layers at
once, for tests and tools.

Prefix sharing (docs/serving.md "Prefix cache"): every allocated block
carries a refcount, and blocks that hold a FULL block of prompt tokens
are published into a hash-chain index (``h_i = sha256(h_{i-1} ||
tokens[i*bs:(i+1)*bs])``) once their owner finishes prefill. A later
request whose prompt starts with the same token blocks takes shared
read-only references instead of re-paying prefill compute and KV
memory; at the divergence block a copy-on-write fork copies the common
row prefix into a private block, so the writer never mutates shared
state. Zero-ref published blocks stay resident as an LRU *prefix
cache* (reclaimed on demand — they count as free for admission);
blocks a quarantined tenant dirtied are scrubbed before any reuse
(the PR-9 NaN-scrub rule lifted to refcounted blocks: refcount zero →
scrub → free list).

Recurrent state (docs/serving.md "Recurrent state"): a model with
state-space layers holds, beside the K/V of its attention layers, a
state of FIXED size a sequence. The paged pool then has only the layers
that HAVE keys (``num_layers`` here is the model's ``num_kv_layers``:
the model maps its layer to the pool's), and beside it lies a pool of
*state slots*,

    state: (state_slots + 1, state layers, ...) an array of the state

one slot a sequence, taken at :meth:`KVCache.allocate` /
:meth:`KVCache.allocate_prefix`, returned at :meth:`KVCache.free`, the
last one the trash slot of dummy lanes. ``KVCacheState.state`` holds
those arrays and is ``None`` for a model without such layers (no leaf:
its programs are what they were). A prompt of such a model takes NO
prefix match and publishes nothing: a shared block says nothing of the
state at its end.

Reservation is staged: :meth:`KVCache.allocate_prefix` reserves only
the span the caller names (a prefill chunk, or the full prompt +
max_new span), and :meth:`KVCache.extend` grows the reservation
chunk-by-chunk — the scheduler reserves the decode span (prompt +
max_new) together with the LAST chunk, so a request that reaches
DECODING still can never die of pool exhaustion mid-decode
(docs/serving.md "admission control").
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

import numpy as np

TRASH_BLOCK = 0


class PoolExhausted(RuntimeError):
    """Admission refused: the pool cannot reserve the requested span.

    Carries ``needed`` / ``free`` block counts so the scheduler can
    tell a transient full pool (wait) from an impossible request
    (``needed > capacity``: reject)."""

    def __init__(self, msg: str, *, needed: int, free: int, capacity: int):
        super().__init__(msg)
        self.needed = int(needed)
        self.free = int(free)
        self.capacity = int(capacity)


class KVCacheState(NamedTuple):
    """The device-side pools — a pytree the decode step DONATES, so
    appends run in place and the cache never holds two copies."""

    k: Any    # (num_layers, num_blocks, block_size, kv_heads, head_dim)
    v: Any
    # the recurrent state's pools, a tuple of (state_slots + 1, state
    # layers, ...) arrays; None (no leaf) for a model all of whose
    # layers have keys
    state: Any = None


class PrefixMatch(NamedTuple):
    """What :meth:`KVCache.allocate_prefix` matched for a prompt.

    ``matched`` tokens of the prompt are already resident (shared
    full blocks + ``fork_rows`` copied rows of the divergence block) —
    prefill resumes at position ``matched``. ``copies`` are the pending
    COW row copies ``(src_block, dst_block, rows)`` the engine must
    execute on the device state BEFORE the sequence's first chunk
    (``apply_copies``); until :meth:`KVCache.fork_copied` runs, the
    source blocks hold an extra reference so they cannot be evicted or
    scrubbed out from under the copy."""

    matched: int
    shared_blocks: int
    fork_rows: int
    copies: Tuple[Tuple[int, int, int], ...]


class KVCache:
    """Block allocator + pool factory for one model's KV cache.

    ``num_blocks`` counts usable blocks *excluding* the trash block
    (the pool array holds ``num_blocks + 1``).

    Thread-safety contract: every ALLOCATOR method (allocate / free /
    table / table_array / can_admit and the counters) takes this
    cache's internal lock, so a client thread calling
    ``ContinuousBatcher.submit()`` and the engine thread admitting,
    finishing, or draining can interleave freely. The device POOLS
    (``init_state()``'s arrays) are not covered: they are owned by the
    engine thread and donated through each prefill/decode dispatch —
    nothing else may touch them mid-step.
    """

    def __init__(self, num_layers: int, kv_heads: int, head_dim: int, *,
                 num_blocks: int, block_size: int = 16,
                 dtype: Any = None, state_slots: int = 0,
                 state_shapes: Sequence[Tuple[Tuple[int, ...], Any]] = ()):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if bool(state_slots) != bool(state_shapes):
            raise ValueError(
                "state_slots and state_shapes go together: a model with "
                "recurrent layers needs a slot a sequence, and no other "
                f"model has any (got {state_slots} slots for "
                f"{len(state_shapes)} state arrays)")
        import jax.numpy as jnp

        self.num_layers = int(num_layers)
        self.kv_heads = int(kv_heads)
        self.head_dim = int(head_dim)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = dtype if dtype is not None else jnp.float32
        self._lock = threading.Lock()
        # LIFO free list: a freed sequence's blocks are the next handed
        # out (reuse-after-free is the common case under steady load,
        # and LIFO keeps the hot blocks hot)
        self._free: List[int] = list(range(self.num_blocks, 0, -1))
        self._tables: Dict[Any, List[int]] = {}
        # -- prefix-sharing plane (module docstring) -------------------
        self._refs: Dict[int, int] = {}          # block -> refcount
        # published block -> (chain hash, parent hash, block tokens)
        self._meta: Dict[int, Tuple[bytes, bytes, Tuple[int, ...]]] = {}
        self._index: Dict[bytes, int] = {}       # chain hash -> block
        self._children: Dict[bytes, List[int]] = {}
        # zero-ref published blocks, LRU order (prefix cache — these
        # count as reclaimable for admission)
        self._cached: "OrderedDict[int, bytes]" = OrderedDict()
        self._dirty: Set[int] = set()
        self._pending_scrub: List[int] = []      # zero-ref dirty blocks
        self._fork_refs: Dict[Any, List[int]] = {}
        # -- recurrent state (module docstring) ------------------------
        self.state_slots = int(state_slots)
        self.state_shapes = tuple((tuple(shape), dtype)
                                  for shape, dtype in state_shapes)
        self._free_slots: List[int] = list(range(self.state_slots - 1,
                                                 -1, -1))
        self._slots: Dict[Any, int] = {}
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_saved = 0

    @classmethod
    def for_config(cls, cfg, *, num_blocks: int, block_size: int = 16,
                   dtype: Any = None, state_slots: int = 0) -> "KVCache":
        """Size the cache from a ``GPTConfig``-shaped model config:
        ``kv_heads`` (the GQA-narrowed count) x ``head_dim`` blocks —
        GQA pays GQA-sized blocks, never ``num_heads``-sized ones. The
        head size is the config's own ``head_dim`` where it has one
        (it need not be ``hidden_size / num_heads``). The pool has the
        layers that have keys (``num_kv_layers`` where the config
        tells them apart), and ``state_slots`` slots of the config's
        ``state_shapes()`` where it has recurrent layers."""
        head_dim = getattr(cfg, "head_dim", None)
        shapes = cfg.state_shapes() if hasattr(cfg, "state_shapes") else ()
        return cls(getattr(cfg, "num_kv_layers", cfg.num_layers),
                   cfg.kv_heads,
                   head_dim or cfg.hidden_size // cfg.num_heads,
                   num_blocks=num_blocks, block_size=block_size,
                   dtype=dtype if dtype is not None else cfg.dtype,
                   state_slots=state_slots, state_shapes=shapes)

    # -- pool ---------------------------------------------------------------

    def init_state(self) -> KVCacheState:
        """Allocate the pools (once; +1 block for the trash block)."""
        import jax.numpy as jnp

        shape = (self.num_layers, self.num_blocks + 1, self.block_size,
                 self.kv_heads, self.head_dim)
        state = tuple(jnp.zeros((self.state_slots + 1, *shape), dtype)
                      for shape, dtype in self.state_shapes) or None
        return KVCacheState(k=jnp.zeros(shape, self.dtype),
                            v=jnp.zeros(shape, self.dtype), state=state)

    def slot_bytes(self) -> int:
        """Bytes of one sequence's recurrent state (0 without any)."""
        import jax.numpy as jnp

        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.state_shapes)

    def block_bytes(self) -> int:
        """Bytes of one block, K and V, over the pool's layers."""
        import jax.numpy as jnp

        return (2 * self.num_layers * self.block_size * self.kv_heads
                * self.head_dim * jnp.dtype(self.dtype).itemsize)

    def pool_bytes(self) -> int:
        import jax.numpy as jnp

        n = (self.num_layers * (self.num_blocks + 1) * self.block_size
             * self.kv_heads * self.head_dim)
        return 2 * n * jnp.dtype(self.dtype).itemsize

    # -- allocator ----------------------------------------------------------

    def blocks_for(self, total_len: int) -> int:
        """Blocks a sequence of ``total_len`` tokens occupies."""
        return -(-max(int(total_len), 1) // self.block_size)

    def can_admit(self, total_len: int) -> bool:
        with self._lock:
            return self.blocks_for(total_len) <= self._reclaimable()

    def _reclaimable(self) -> int:
        # free list + the zero-ref prefix cache (evictable on demand)
        return len(self._free) + len(self._cached)

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return self._reclaimable()

    @property
    def blocks_in_use(self) -> int:
        """Blocks referenced by live sequences (cached prefix blocks
        and pending-scrub blocks are reclaimable, not in use)."""
        with self._lock:
            return len(self._refs)

    @property
    def slots_in_use(self) -> int:
        with self._lock:
            return len(self._slots)

    def _take_slot(self, seq_id) -> None:
        """A state slot for ``seq_id``, where the model has recurrent
        layers; before any block is taken, so a refusal leaks nothing.
        Caller holds the lock."""
        if not self.state_slots:
            return
        if not self._free_slots:
            raise PoolExhausted(
                f"state slots exhausted: sequence {seq_id!r} needs one, "
                f"all {self.state_slots} are held",
                needed=1, free=0, capacity=self.state_slots)
        self._slots[seq_id] = self._free_slots.pop()

    def _drop_slot(self, seq_id) -> None:
        slot = self._slots.pop(seq_id, None)
        if slot is not None:
            self._free_slots.append(slot)

    def slot_array(self, seq_ids: Sequence[Any],
                   batch: Optional[int] = None) -> np.ndarray:
        """The batch's state slots, ``(batch,)`` int32; dummy rows past
        ``len(seq_ids)`` name the trash slot."""
        b = len(seq_ids) if batch is None else int(batch)
        out = np.full((b,), self.state_slots, np.int32)
        with self._lock:
            for i, sid in enumerate(seq_ids):
                out[i] = self._slots[sid]
        return out

    def _take_private(self, need: int, seq_id) -> List[int]:
        """Pop ``need`` fresh private blocks — free list first, then
        evict the LRU tail of the prefix cache. Caller holds the
        lock."""
        if need > self._reclaimable():
            raise PoolExhausted(
                f"kv pool exhausted: sequence {seq_id!r} needs {need} "
                f"blocks, {self._reclaimable()} free of "
                f"{self.num_blocks}",
                needed=need, free=self._reclaimable(),
                capacity=self.num_blocks)
        out: List[int] = []
        for _ in range(need):
            if self._free:
                out.append(self._free.pop())
            else:
                blk, _h = self._cached.popitem(last=False)   # LRU evict
                self._unpublish(blk)
                out.append(blk)
        for b in out:
            self._refs[b] = 1
        return out

    def allocate(self, seq_id, total_len: int) -> List[int]:
        """Reserve the full block span for a sequence reaching
        ``total_len`` tokens; raises :class:`PoolExhausted` when the
        free list can't cover it (the admission-control refusal).
        Private blocks only — the prefix-aware admit path is
        :meth:`allocate_prefix`."""
        need = self.blocks_for(total_len)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id!r} already allocated")
            self._take_slot(seq_id)
            try:
                blocks = self._take_private(need, seq_id)
            except PoolExhausted:
                self._drop_slot(seq_id)
                raise
            self._tables[seq_id] = blocks
            return list(blocks)

    def allocate_prefix(self, seq_id, prompt: Sequence[int],
                        total_len: int,
                        chunk: Optional[int] = None) -> PrefixMatch:
        """Reserve blocks for a sequence whose prompt is ``prompt``,
        reusing published prefix blocks by reference and COW-forking
        the divergence block.

        ``total_len`` is the full span (prompt + max_new). With
        ``chunk=None`` the whole span is reserved up front (the
        monolithic-admit contract); with a chunk size, reservation is
        STAGED — only ``matched + chunk`` tokens are covered now (the
        full span when that already reaches the end of the prompt),
        and the scheduler grows it via :meth:`extend` chunk by chunk.

        At most ``len(prompt) - 1`` tokens ever match (the last prompt
        token always prefills, so the first-token logits exist).
        Raises :class:`PoolExhausted` (leaking nothing) when the
        private remainder cannot be reserved.

        A model with recurrent layers matches NOTHING, whatever is
        published (and nothing is: :meth:`publish_prefix`): a shared
        block holds keys, and says nothing of the state at its end.
        The sequence takes its state slot here.
        """
        bs = self.block_size
        if not self.state_slots:
            prompt = tuple(int(t) for t in prompt)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id!r} already allocated")
            hashes = [] if self.state_slots else self._chain_hashes(prompt)
            max_full = (len(prompt) - 1) // bs
            shared: List[int] = []
            parent = b""
            for i in range(min(len(hashes), max_full)):
                blk = self._index.get(hashes[i])
                if blk is None or blk in self._dirty:
                    break
                shared.append(blk)
                parent = hashes[i]
            m = len(shared)
            # COW fork: longest common row prefix with a published
            # child of the matched chain (leave >= 1 token to prefill)
            fork_src, fork_rows = None, 0
            budget = len(prompt) - 1 - m * bs
            if budget > 0:
                want = prompt[m * bs: (m + 1) * bs]
                for cand in self._children.get(parent, ()):
                    if cand in self._dirty or cand not in self._meta:
                        continue
                    toks = self._meta[cand][2]
                    f = 0
                    for a, c in zip(toks, want):
                        if a != c:
                            break
                        f += 1
                    f = min(f, budget)
                    if f > fork_rows:
                        fork_src, fork_rows = cand, f
            matched = m * bs + fork_rows
            if chunk is None or matched + chunk >= len(prompt):
                reserve_len = total_len
            else:
                reserve_len = matched + chunk      # staged: first chunk
            need = self.blocks_for(reserve_len) - m
            if need < 0:
                need = 0
            if fork_rows and need < 1:
                need = 1                     # the fork's private block
            # reference the matched blocks FIRST: _take_private evicts
            # the cached LRU, and a matched-but-unreferenced block
            # must not be evicted out from under this admission
            for blk in shared:
                self._ref_locked(blk)
            if fork_rows:
                self._ref_locked(fork_src)   # pin src until the copy
            try:
                self._take_slot(seq_id)
                priv = self._take_private(need, seq_id)
            except PoolExhausted:
                self._drop_slot(seq_id)
                for blk in shared:           # leak nothing on refusal
                    self._unref_locked(blk, dirty=False)
                if fork_rows:
                    self._unref_locked(fork_src, dirty=False)
                raise
            copies: Tuple[Tuple[int, int, int], ...] = ()
            if fork_rows:
                self._fork_refs[seq_id] = [fork_src]
                copies = ((fork_src, priv[0], fork_rows),)
            self._tables[seq_id] = shared + priv
            if matched > 0:
                self.prefix_hits += 1
                self.prefix_tokens_saved += matched
            else:
                self.prefix_misses += 1
            return PrefixMatch(matched=matched, shared_blocks=m,
                               fork_rows=fork_rows, copies=copies)

    def extend(self, seq_id, total_len: int) -> int:
        """Grow a sequence's reservation to cover ``total_len`` tokens
        (staged per-chunk reservation); returns how many NEW private
        blocks were appended. Raises :class:`PoolExhausted` leaving
        the existing reservation intact."""
        with self._lock:
            table = self._tables[seq_id]
            need = self.blocks_for(total_len) - len(table)
            if need <= 0:
                return 0
            table.extend(self._take_private(need, seq_id))
            return need

    def fork_copied(self, seq_id) -> None:
        """Drop the pin on a COW fork's source blocks (the engine has
        executed the row copies on the device state)."""
        with self._lock:
            for blk in self._fork_refs.pop(seq_id, []):
                self._unref_locked(blk, dirty=False)

    def free(self, seq_id, *, dirty: bool = False,
             clean_blocks: Sequence[int] = ()) -> int:
        """Return a sequence's block references to the pool; returns
        how many blocks were released.

        ``dirty=True`` (the quarantine path) marks every released
        block — except ``clean_blocks``, which the caller already
        scrubbed device-side — as poisoned: it is unpublished at once
        (never matched again) and, when its refcount reaches zero,
        parked on the pending-scrub list instead of the free list
        until :meth:`scrub_done` confirms the device rows were zeroed
        (refcount zero -> scrub -> reuse)."""
        clean = set(int(b) for b in clean_blocks)
        with self._lock:
            blocks = self._tables.pop(seq_id, None)
            if blocks is None:
                return 0
            self._drop_slot(seq_id)
            for blk in self._fork_refs.pop(seq_id, []):
                self._unref_locked(blk, dirty=False)
            for b in blocks:
                self._unref_locked(b, dirty=dirty and b not in clean)
            return len(blocks)

    def _ref_locked(self, blk: int) -> None:
        if blk in self._refs:
            self._refs[blk] += 1
            return
        # revive a zero-ref cached prefix block
        self._cached.pop(blk, None)
        self._refs[blk] = 1

    def _unref_locked(self, blk: int, *, dirty: bool) -> None:
        if dirty and blk not in self._dirty:
            self._dirty.add(blk)
            self._unpublish(blk)             # never matched again
        self._refs[blk] -= 1
        if self._refs[blk] > 0:
            return
        del self._refs[blk]
        if blk in self._dirty:
            self._pending_scrub.append(blk)
        elif blk in self._meta:
            self._cached[blk] = self._meta[blk][0]
            self._cached.move_to_end(blk)
        else:
            self._free.append(blk)

    def _unpublish(self, blk: int) -> None:
        meta = self._meta.pop(blk, None)
        if meta is None:
            return
        h, parent, _toks = meta
        if self._index.get(h) == blk:
            del self._index[h]
        kids = self._children.get(parent)
        if kids and blk in kids:
            kids.remove(blk)
            if not kids:
                del self._children[parent]
        self._cached.pop(blk, None)

    def _chain_hashes(self, prompt: Tuple[int, ...]) -> List[bytes]:
        bs = self.block_size
        out: List[bytes] = []
        h = b""
        for i in range(len(prompt) // bs):
            blk = np.asarray(prompt[i * bs:(i + 1) * bs],
                             np.int64).tobytes()
            h = hashlib.sha256(h + blk).digest()
            out.append(h)
        return out

    def publish_prefix(self, seq_id, prompt: Sequence[int]) -> int:
        """Publish a fully-prefilled sequence's full prompt blocks into
        the prefix index (later prompts with the same token blocks
        share them by reference); returns how many blocks were newly
        published. First publisher wins — blocks whose chain hash is
        already indexed are left alone. A model with recurrent layers
        publishes nothing (:meth:`allocate_prefix`)."""
        if self.state_slots:
            return 0
        prompt = tuple(int(t) for t in prompt)
        bs = self.block_size
        published = 0
        with self._lock:
            table = self._tables.get(seq_id)
            if table is None:
                return 0
            hashes = self._chain_hashes(prompt)
            parent = b""
            for i, h in enumerate(hashes):
                blk = table[i]
                if blk in self._dirty:
                    break
                if h in self._index:
                    parent = h
                    continue                 # first publisher wins
                if blk in self._meta:        # published under another
                    parent = h               # chain (shared-in block)
                    continue
                self._meta[blk] = (h, parent,
                                   prompt[i * bs:(i + 1) * bs])
                self._index[h] = blk
                self._children.setdefault(parent, []).append(blk)
                parent = h
                published += 1
            return published

    def take_pending_scrub(self) -> List[int]:
        """Pop the zero-ref dirty blocks awaiting a device scrub; the
        engine must zero their pool rows and call :meth:`scrub_done`
        before they can be reused."""
        with self._lock:
            out, self._pending_scrub = self._pending_scrub, []
            return out

    def scrub_done(self, blocks: Sequence[int]) -> None:
        """Return device-scrubbed blocks to the free list."""
        with self._lock:
            for b in blocks:
                self._dirty.discard(b)
                self._free.append(b)

    def reset_prefix_cache(self) -> int:
        """Drop every zero-ref cached prefix block back to the free
        list and clear the index (bench runs isolate workloads this
        way); returns how many blocks were reclaimed."""
        with self._lock:
            n = len(self._cached)
            for blk in list(self._cached):
                self._unpublish(blk)
                self._free.append(blk)
            self._cached.clear()
            self.prefix_hits = 0
            self.prefix_misses = 0
            self.prefix_tokens_saved = 0
            return n

    def prefix_match_len(self, prompt: Sequence[int]) -> int:
        """How many leading prompt tokens the prefix index could hand
        out by REFERENCE right now: full published, non-dirty blocks
        along the prompt's sha256 hash chain (capped at
        ``len(prompt) - 1`` like :meth:`allocate_prefix`; COW-fork
        partial rows are not counted — this is a cheap placement
        probe, not a reservation). Read-only: nothing is referenced,
        revived, or evicted. The fleet router's prefix-affinity score
        (serving/fleet.py): the engine whose pool already holds the
        longest prefix wins the request."""
        prompt = tuple(int(t) for t in prompt)
        with self._lock:
            hashes = self._chain_hashes(prompt)
            max_full = (len(prompt) - 1) // self.block_size
            m = 0
            for i in range(min(len(hashes), max_full)):
                blk = self._index.get(hashes[i])
                if blk is None or blk in self._dirty:
                    break
                m += 1
            return m * self.block_size

    def prefix_stats(self) -> Dict[str, int]:
        """Prefix-cache accounting for gauges/flight bundles."""
        with self._lock:
            shared = sum(1 for r in self._refs.values() if r > 1)
            return {
                "cached_blocks": len(self._cached),
                "shared_blocks": shared,
                "published_blocks": len(self._meta),
                "pending_scrub": len(self._pending_scrub),
                "hits": self.prefix_hits,
                "misses": self.prefix_misses,
                "tokens_saved": self.prefix_tokens_saved,
            }

    def block_ref(self, blk: int) -> int:
        with self._lock:
            return self._refs.get(int(blk), 0)

    def exclusive_blocks(self, seq_id) -> List[int]:
        """Blocks only this sequence references and nobody can match
        from the index — safe to scrub immediately on quarantine."""
        with self._lock:
            return [b for b in self._tables.get(seq_id, [])
                    if self._refs.get(b) == 1 and b not in self._meta]

    def table(self, seq_id) -> List[int]:
        with self._lock:
            return list(self._tables[seq_id])

    @property
    def sequences(self) -> List[Any]:
        with self._lock:
            return list(self._tables)

    def table_array(self, seq_ids: Sequence[Any], width: int,
                    batch: Optional[int] = None) -> np.ndarray:
        """The batch's block tables as one right-padded ``(batch,
        width)`` int32 array — padding (and dummy batch rows past
        ``len(seq_ids)``) points at the trash block."""
        b = len(seq_ids) if batch is None else int(batch)
        out = np.full((b, int(width)), TRASH_BLOCK, np.int32)
        with self._lock:
            for i, sid in enumerate(seq_ids):
                t = self._tables[sid]
                if len(t) > width:
                    raise ValueError(
                        f"table width {width} < {len(t)} blocks of "
                        f"sequence {sid!r}")
                out[i, :len(t)] = t
        return out

    def window_width(self, window: int, width: int) -> int:
        """Blocks in a window layer's table when the full table has
        ``width``: the ``window`` positions plus one block for the
        edge, rounded up to a quarter of the window (4096 positions
        gather 5120: five of the flash kernel's key blocks of 1024,
        where 4112 would leave it blocks of 16), and never more than
        the full table."""
        bs = self.block_size
        quarter = max(1, window // (4 * bs))
        blocks = -(-(window + bs) // bs)
        return min(int(width), -(-blocks // quarter) * quarter)

    def window_table_array(self, seq_ids: Sequence[Any], positions,
                           window: int, width: int,
                           batch: Optional[int] = None):
        """The tails of the batch's block tables for a window layer:
        ``(tables (batch, width), first (batch,))``. Lane ``i`` is
        about to attend from position ``positions[i]`` on (a decode
        token's own position, a chunk's start); the oldest key any of
        its queries sees is ``positions[i] - window + 1``, in block
        ``first[i]`` of the lane's own table, and the tail holds that
        block and the ``width - 1`` after it. What the sequence has
        not reached, and dummy rows, point at the trash block."""
        b = len(seq_ids) if batch is None else int(batch)
        out = np.full((b, int(width)), TRASH_BLOCK, np.int32)
        first = np.zeros((b,), np.int32)
        with self._lock:
            for i, sid in enumerate(seq_ids):
                t = self._tables[sid]
                lo = max(0, int(positions[i]) - window + 1) // self.block_size
                if int(positions[i]) // self.block_size >= lo + width:
                    raise ValueError(
                        f"window table width {width} does not reach "
                        f"position {int(positions[i])} of sequence {sid!r}")
                tail = t[lo:lo + width]
                out[i, :len(tail)] = tail
                first[i] = lo
        return out, first

    # -- disaggregated handoff (serving/fleet.py) --------------------------

    def export_blocks(self, state: KVCacheState, seq_id, *,
                      length: Optional[int] = None
                      ) -> Tuple[List[int], np.ndarray, np.ndarray]:
        """Extract a sequence's KV rows to the host for a cross-engine
        handoff: ``(blocks, k, v)`` where ``blocks`` is the sequence's
        block table (source indices, for the manifest) and ``k``/``v``
        are ``(num_layers, n, block_size, kv_heads, head_dim)`` host
        arrays. ``length`` bounds the export to the blocks that
        actually hold tokens (``blocks_for(length)``) so the wire never
        carries the unwritten decode-span tail; ``None`` exports the
        whole reservation. Read-only on both the table (a locked copy)
        and the pool — shared prefix blocks export fine."""
        table = self.table(seq_id)           # locked copy; raises unknown
        if length is not None:
            table = table[:self.blocks_for(length)]
        idx = np.asarray(table, np.int32)
        return (list(table), np.asarray(state.k[:, idx]),
                np.asarray(state.v[:, idx]))

    def export_state(self, state: KVCacheState, seq_id):
        """A sequence's recurrent state to the host, for the same
        handoff: a tuple of ``(state layers, ...)`` arrays, one a state
        pool, or None where the model has no such layers."""
        if not self.state_slots:
            return None
        slot = int(self.slot_array([seq_id])[0])
        return tuple(np.asarray(pool[slot]) for pool in state.state)

    def import_state(self, state: KVCacheState, seq_id,
                     rows) -> KVCacheState:
        """Install :meth:`export_state`'s rows into the slot of an
        already-allocated ``seq_id``."""
        import jax.numpy as jnp

        if not self.state_slots:
            return state
        if rows is None or len(rows) != len(state.state):
            raise ValueError(
                f"import_state: sequence {seq_id!r} needs its recurrent "
                f"state ({len(state.state)} arrays), the payload holds "
                f"{0 if rows is None else len(rows)}")
        slot = int(self.slot_array([seq_id])[0])
        return state._replace(state=tuple(
            pool.at[slot].set(jnp.asarray(r, pool.dtype))
            for pool, r in zip(state.state, rows)))

    def import_blocks(self, state: KVCacheState, seq_id, k,
                      v) -> KVCacheState:
        """Install exported KV rows into THIS pool's blocks for an
        already-allocated ``seq_id`` (the receiving side of a handoff):
        row block ``i`` of the payload lands in the sequence's block
        ``table[i]``. The verify-before-install discipline is the
        CALLER's (serving/fleet.py hashes every block against the
        manifest first) — this method trusts its inputs. Returns the
        new device state; the table/refcounts are untouched."""
        import jax.numpy as jnp

        table = self.table(seq_id)
        k = np.asarray(k)
        v = np.asarray(v)
        n = k.shape[1]
        if n > len(table):
            raise ValueError(
                f"import_blocks: payload holds {n} blocks but sequence "
                f"{seq_id!r} reserves only {len(table)}")
        idx = jnp.asarray(table[:n], jnp.int32)
        return state._replace(
            k=state.k.at[:, idx].set(jnp.asarray(k, state.k.dtype)),
            v=state.v.at[:, idx].set(jnp.asarray(v, state.v.dtype)))


def bucket(n: int, minimum: int = 1) -> int:
    """Next power of two >= max(n, minimum) — the shape-bucketing that
    bounds the decode compile count (docs/serving.md)."""
    b = max(int(minimum), 1)
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# Traced pool ops (what the jitted prefill/decode programs call)
# ---------------------------------------------------------------------------


def gather_kv(state: KVCacheState, tables):
    """Gather each sequence's blocks into contiguous per-batch views,
    all layers at once.

    ``tables`` (batch, width) int32 -> two ``(num_layers, batch,
    kv_heads, width * block_size, head_dim)`` arrays. Pure data
    movement — the bytes written by :func:`append_kv` come back
    bitwise (tests/test_serving.py pins it). Unallocated table entries
    gather the trash block; the caller's attention mask drops them.

    The pool's read contract, for tests and tools. The compiled
    programs do not call it — a copy of every layer's context is what
    they avoid: each layer gathers its own, layer ``l`` of this result
    bitwise, inside the layer scan (``ops/kv_gather.py`` through the
    model's ``kv_ctx`` hook).
    """
    def one(pool):
        g = pool[:, tables]            # (L, b, w, bs, kv, d)
        layers, b, w, bs, kv, d = g.shape
        return g.transpose(0, 1, 4, 2, 3, 5).reshape(layers, b, kv,
                                                     w * bs, d)
    return one(state.k), one(state.v)


def append_kv(state: KVCacheState, k_new, v_new, tables,
              positions) -> KVCacheState:
    """Write one token's K/V per sequence into the pool in place.

    ``k_new``/``v_new`` (num_layers, batch, kv_heads, head_dim);
    ``positions`` (batch,) the 0-based slot each token lands in. Rows
    whose table entry is the trash block (dummy batch slots) write
    harmlessly into it.

    One update in place a lane and pool, all layers of the lane's
    ``(1, 1, kv_heads, head_dim)`` rows at once, which leaves the pool
    in the layout it arrived in (some 2 us an update on a v5e). One
    scatter over all lanes is laid out apart from a pool whose rows
    are not whole (8, 128) tiles (4 KV heads of bf16 fill half a
    tile): the TPU compiler then converts the K and V pools whole,
    there and back, in every call (PERF.md, PR 34).
    """
    import jax.numpy as jnp
    from jax import lax

    bs = state.k.shape[2]
    w = tables.shape[1]
    blk = jnp.take_along_axis(
        tables, jnp.clip(positions[:, None] // bs, 0, w - 1), axis=1)[:, 0]
    slot = positions % bs
    k, v = state.k, state.v
    for i in range(tables.shape[0]):
        at = (0, blk[i], slot[i], 0, 0)
        k = lax.dynamic_update_slice(
            k, k_new[:, i, None, None].astype(k.dtype), at)
        v = lax.dynamic_update_slice(
            v, v_new[:, i, None, None].astype(v.dtype), at)
    return state._replace(k=k, v=v)


def append_kv_prefill(state: KVCacheState, k_new, v_new, tables,
                      lengths) -> KVCacheState:
    """Write a whole prompt's K/V per sequence into the pool in place.

    ``k_new``/``v_new`` (num_layers, batch, kv_heads, seq, head_dim)
    right-padded; the pads' garbage K/V at positions ``>= lengths``
    never lands in a real block.
    """
    return append_kv_chunk(state, k_new, v_new, tables, None, lengths)


def append_kv_chunk(state: KVCacheState, k_new, v_new, tables, starts,
                    lengths) -> KVCacheState:
    """Write one prefill CHUNK's K/V per sequence into the pool.

    The chunk-resumable generalization of :func:`append_kv_prefill`:
    chunk row ``i`` of sequence ``b`` lands at global position
    ``starts[b] + i`` (``starts=None`` means 0 — the monolithic
    prefill; ``starts >= 0``). Rows ``i >= lengths[b]`` (chunk
    padding) never land in a real block.

    A loop over the lanes and, inside it, over the blocks a lane's
    valid rows touch (none for a dummy lane), each turn an update in
    place as in :func:`append_kv`: it takes the block's rows out of
    the pool, puts the chunk's valid rows over them and writes the
    block back. So the rows before a chunk that starts inside a block
    (a prefix-cache fork: :func:`apply_copies`) stay, as do those
    after a chunk that ends inside one, and the pads are not written
    at all.
    """
    import jax.numpy as jnp
    from jax import lax

    layers, _, bs, kv, d = state.k.shape
    b, w = tables.shape
    s = k_new.shape[3]

    def rows(new):
        # (L, b, kv, s, d) -> (L, b, bs + s + pad, kv, d): rows as the
        # pool holds them, a block of pads in front (a chunk may start
        # inside a block) and behind, so that every block's slice of
        # the chunk lies inside the array
        pad = (bs, (-(-s // bs) + 1) * bs - s)
        return jnp.pad(new.transpose(0, 1, 3, 2, 4).astype(state.k.dtype),
                       ((0, 0), (0, 0), pad, (0, 0), (0, 0)))

    k_new, v_new = rows(k_new), rows(v_new)
    slot = jnp.arange(bs, dtype=jnp.int32)
    if starts is None:
        starts = jnp.zeros((b,), jnp.int32)

    def lane(i, pools):
        n = jnp.minimum(lengths[i], s)
        block, off = lax.div(starts[i], bs), lax.rem(starts[i], bs)

        def turn(j, pools):
            first = j * bs - off                # the chunk row of slot 0
            valid = (first + slot >= 0) & (first + slot < n)
            at = (0, tables[i, jnp.clip(block + j, 0, w - 1)], 0, 0, 0)

            def one(pool, new):
                old = lax.dynamic_slice(pool, at, (layers, 1, bs, kv, d))
                new = lax.dynamic_slice(new, (0, i, bs + first, 0, 0),
                                        (layers, 1, bs, kv, d))
                return lax.dynamic_update_slice(
                    pool, jnp.where(valid[:, None, None], new, old), at)

            return one(pools[0], k_new), one(pools[1], v_new)

        touched = jnp.where(n > 0, lax.div(off + n + bs - 1, bs), 0)
        return lax.fori_loop(0, touched, turn, pools)

    k, v = lax.fori_loop(0, b, lane, (state.k, state.v))
    return state._replace(k=k, v=v)


def apply_copies(state: KVCacheState,
                 copies: Sequence[Tuple[int, int, int]]) -> KVCacheState:
    """Execute COW fork row copies ``(src_block, dst_block, rows)`` on
    the device pools (host-issued between dispatches): the first
    ``rows`` rows of ``src`` — the common token prefix with the
    divergence block — are copied into the fresh private ``dst``; the
    shared source is never written."""
    k, v = state.k, state.v
    for src, dst, rows in copies:
        rows = int(rows)
        k = k.at[:, int(dst), :rows].set(k[:, int(src), :rows])
        v = v.at[:, int(dst), :rows].set(v[:, int(src), :rows])
    return state._replace(k=k, v=v)


def scrub_blocks(state: KVCacheState, blocks) -> KVCacheState:
    """Zero the named pool blocks (the quarantine / pending-scrub
    device op — a freed NaN row must never haunt the next tenant)."""
    import jax.numpy as jnp

    if len(blocks) == 0:
        return state
    b = jnp.asarray(sorted(int(x) for x in blocks), jnp.int32)
    return state._replace(k=state.k.at[:, b].set(0),
                          v=state.v.at[:, b].set(0))


__all__ = [
    "KVCache",
    "KVCacheState",
    "PoolExhausted",
    "PrefixMatch",
    "TRASH_BLOCK",
    "append_kv",
    "append_kv_chunk",
    "append_kv_prefill",
    "apply_copies",
    "bucket",
    "gather_kv",
    "scrub_blocks",
]
