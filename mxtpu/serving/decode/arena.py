"""SequenceSlotArena: fixed-capacity device-resident sequence state.

Autoregressive decode carries per-request recurrent state (RNN
hidden/cell stacks) across continuous-batch iterations. Round-tripping
that state through the host every step would cost two transfers per
token per sequence; the arena instead keeps ONE device array per state
leaf, shaped ``(capacity,) + per_sequence_shape``, and moves only slot
*indices* across the host boundary:

* ``allocate``/``release`` manage a host-side free list of slot ids —
  a sequence owns one slot from admission to eviction;
* ``gather(slots, fresh)`` pulls the active rows into a
  ``(bucket, ...)`` batch for the step program. Freshly admitted
  sequences are zeroed IN the gathered batch (the ``fresh`` mask):
  the arena never needs a separate per-join reset dispatch, so a join
  costs nothing beyond the step it rides;
* ``scatter(slots, new_states)`` writes the step's updated state back.
  Padding rows carry the out-of-bounds index ``capacity`` and are
  DROPPED by the scatter, so a padded batch can never corrupt a live
  slot; the old arena buffers are donated, so the update is in-place
  on device.

Gather/scatter are jitted per bucket size through the compile seam
(``record_program_build``, kind ``decode_state``), so they appear in
the diagnostics program table with AOT cost rows like any other
program. Every arena buffer is accounted in the device-memory ledger
under the ``decode_state`` origin — ``/debug/state`` and ``mxtpu_top``
show exactly what sequence state costs, and the chaos tests assert it
returns to baseline when the arena closes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from ... import diagnostics as _diag
from ...analysis import concurrency as _conc
from ...base import MXNetError
from ...compile import pipeline as _pipeline

__all__ = ["SequenceSlotArena", "PagedArena"]


class SequenceSlotArena:
    """Device-resident per-sequence state store with slot allocation.

    Parameters
    ----------
    capacity : int — maximum concurrently in-flight sequences
    state_specs : list of ``{"name", "shape", "dtype"}`` dicts (the
        :meth:`~mxtpu.rnn.BaseRNNCell.state_spec` format at batch 1,
        or any per-sequence trailing shape with a leading dim of 1)
    ctx : Context the state lives on (default: current context)
    dtype : overrides every spec's dtype when given (the bf16-pipeline
        deployments may keep state in the pipeline dtype)
    """

    def __init__(self, capacity, state_specs, ctx=None, dtype=None):
        from ...context import current_context
        if capacity < 1:
            raise MXNetError("SequenceSlotArena needs capacity >= 1")
        if not state_specs:
            raise MXNetError("SequenceSlotArena needs at least one "
                             "state spec")
        self.capacity = int(capacity)
        self._ctx = ctx or current_context()
        self.specs = []
        for s in state_specs:
            shape = tuple(int(d) for d in s["shape"])
            if len(shape) < 1:
                raise MXNetError("state spec %r needs a leading "
                                 "(batch) dim" % (s,))
            self.specs.append({"name": s["name"],
                               "shape": shape[1:],
                               "dtype": str(dtype or s.get("dtype",
                                                           "float32"))})
        dev = self._ctx.jax_device
        with _diag.alloc_origin("decode_state"):
            self._arrays = [
                jax.device_put(jnp.zeros((self.capacity,) + s["shape"],
                                         dtype=s["dtype"]), dev)
                for s in self.specs
            ]
        nbytes = sum(a.nbytes for a in self._arrays)
        # slot accounting: scatter donates and replaces the buffers every
        # step, but their bind-fixed sizes make the ledger entry exact at
        # zero per-step cost (the executor_outputs convention)
        self._mem_slot = _diag.ledger().slot(self, nbytes, "decode_state",
                                             ctx=str(self._ctx))
        self._free = list(range(self.capacity - 1, -1, -1))
        self._lock = _conc.lock("SequenceSlotArena", "_lock")
        # per-bucket jitted gather/scatter, built lazily through the
        # compile seam so each shows up as a `decode_state` program
        self._gather_fns = {}
        self._scatter_fns = {}
        self._closed = False

    # ---------------------------------------------------------- slots
    @property
    def free_slots(self):
        with self._lock:
            return len(self._free)

    @property
    def occupancy(self):
        """Occupied-slot fraction (the ``decode_slot_occupancy`` gauge)."""
        with self._lock:
            return 1.0 - len(self._free) / self.capacity

    def allocate(self):
        """Claim a free slot id, or None when the arena is full. The
        slot's state rows are NOT cleared here — the first gather of a
        fresh sequence zeroes them via the ``fresh`` mask, so admission
        stays a pure host-side bookkeeping operation."""
        with self._lock:
            if self._closed or not self._free:
                return None
            return self._free.pop()

    def release(self, slot):
        """Return ``slot`` to the free list (sequence finished/evicted).
        The next allocation may reuse it on the very next step."""
        slot = int(slot)
        if not 0 <= slot < self.capacity:
            raise MXNetError("release: slot %d out of range [0, %d)"
                             % (slot, self.capacity))
        with self._lock:
            if slot in self._free:
                raise MXNetError("release: slot %d is already free" % slot)
            self._free.append(slot)

    # ------------------------------------------------------- device ops
    def _bucket_fns(self, bucket):
        fns = self._gather_fns.get(bucket)
        if fns is not None:
            return fns, self._scatter_fns[bucket]

        def _gather(arrays, idx, fresh):
            out = []
            for a in arrays:
                g = jnp.take(a, idx, axis=0, mode="clip")
                mask = fresh.reshape((-1,) + (1,) * (g.ndim - 1))
                # fresh rows start from the exact zero begin-state via
                # select, NOT multiply-by-zero: a previous occupant that
                # diverged may have scattered NaN/Inf into the slot, and
                # 0*NaN == NaN would poison every later occupant. Pad
                # rows gather a clipped slot but are zeroed the same way
                out.append(jnp.where(mask > 0,
                                     jnp.zeros((), dtype=g.dtype), g))
            return out

        def _scatter(arrays, idx, new):
            # mode="drop": pad rows carry idx == capacity (out of
            # bounds) and their writes vanish — a padded batch cannot
            # corrupt a live slot. Old buffers are donated: the arena
            # updates in place on device.
            return [a.at[idx].set(n.astype(a.dtype), mode="drop")
                    for a, n in zip(arrays, new)]

        owner = "decode_arena[b=%d]" % bucket
        gfn = _pipeline.record_program_build(
            "decode_state", owner,
            _pipeline.named_jit("arena_gather", _gather))
        sfn = _pipeline.record_program_build(
            "decode_state", owner,
            _pipeline.named_jit("arena_scatter", _scatter, donate_argnums=0))
        self._gather_fns[bucket] = gfn
        self._scatter_fns[bucket] = sfn
        return gfn, sfn

    def gather(self, slots, fresh):
        """Pull the state rows for ``slots`` (int array, pad rows may
        carry any in-range id) into ``(bucket, ...)`` device arrays,
        zeroing rows flagged in ``fresh`` (float 0/1 mask — freshly
        admitted sequences AND pad rows). No host transfer: the result
        feeds the step program directly."""
        # mxtpu: allow-sync(slot ids/masks are host-born ints, never
        # device data — index normalization, not a transfer)
        idx = _np.asarray(slots, dtype=_np.int32)
        # mxtpu: allow-sync(see above — host-born 0/1 mask)
        mask = _np.asarray(fresh, dtype=_np.float32)
        gfn, _ = self._bucket_fns(len(idx))
        return gfn(self._arrays, idx, mask)

    def scatter(self, slots, new_states):
        """Write the step program's updated state rows back into the
        arena at ``slots``; rows whose index is ``capacity`` (padding)
        are dropped. Donates the previous buffers — single-consumer by
        contract (the session's one step loop)."""
        # mxtpu: allow-sync(host-born slot ids — index normalization)
        idx = _np.asarray(slots, dtype=_np.int32)
        _, sfn = self._bucket_fns(len(idx))
        self._arrays = sfn(self._arrays, idx, list(new_states))

    def state_bytes(self):
        """Ledger-visible device bytes of the arena (``decode_state``)."""
        return sum(a.nbytes for a in self._arrays) \
            if self._arrays else 0

    def close(self):
        """Release the device buffers and zero the ledger entry. The
        chaos gate asserts ``decode_state`` returns to its pre-session
        baseline — this is the seam that guarantees it."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._arrays = None
            self._free = []
        self._mem_slot.set(0)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class PagedArena:
    """Block-granular device-resident KV/state store: the vLLM recipe.

    :class:`SequenceSlotArena` sizes every slot for the worst case — a
    sequence three tokens into a 256-token budget owns 256 tokens of
    device state. The paged arena instead keeps each state leaf as ONE
    flat device array of ``blocks_total × block_size`` token rows and
    hands blocks to sequences AS THEY GROW, via a host-side per-slot
    **block table**:

    * ``allocate``/``release`` manage sequence slots exactly like the
      contiguous arena; ``release`` also returns every block in the
      slot's table to the free pool (the no-leak contract rides it);
    * ``ensure_tokens(slot, n)`` grows the slot's table until it covers
      ``n`` token positions — pure host bookkeeping, no device dispatch;
    * ``gather_view(slots)`` assembles the bucketed
      ``(B, max_blocks, block, …)`` cache view the attention step
      program consumes. Table padding carries the out-of-range block id
      ``blocks_total`` (``mode="clip"`` gathers SOME block), so padded
      tail blocks hold garbage BY DESIGN — the step model masks them
      with select-not-multiply and the tests prove they are inert;
    * ``gather_rows``/``scatter_rows`` move single token rows by FLAT
      position (``table[pos//block]·block + pos%block``) — the decode
      step's append and the recurrent-state compatibility path. Padding
      rows carry the out-of-bounds flat index and are dropped
      (``mode="drop"``); scatter donates, updating in place.

    Gather/scatter are jitted per bucket through the compile seam
    (kind ``decode_paged``) and every buffer is accounted under the
    ledger origin ``decode_kv``. The ledger entry tracks the LIVE
    block bytes (``blocks_live × block_bytes`` — the exact-accounting
    gate's basis); the preallocated pool's physical footprint stays
    visible through :meth:`state_bytes`.

    Parameters
    ----------
    capacity : int — maximum concurrently in-flight sequences
    block_size : int — token positions per KV block
    blocks_total : int — blocks in the shared device pool
    max_blocks_per_seq : int — per-slot table bound; also fixes the
        gathered view's ``max_blocks`` axis (a compile-time constant of
        the step program)
    kv_specs : list of ``{"name", "shape", "dtype"}`` — PER-TOKEN
        trailing shape of each state leaf (``(heads, head_dim)`` for a
        KV leaf; the per-sequence state shape for recurrent state
        stored as one-token rows)
    ctx / dtype : as :class:`SequenceSlotArena`
    """

    def __init__(self, capacity, block_size, blocks_total,
                 max_blocks_per_seq, kv_specs, ctx=None, dtype=None):
        from ...context import current_context
        if capacity < 1:
            raise MXNetError("PagedArena needs capacity >= 1")
        if block_size < 1 or blocks_total < 1 or max_blocks_per_seq < 1:
            raise MXNetError("PagedArena needs block_size, blocks_total "
                             "and max_blocks_per_seq >= 1")
        if not kv_specs:
            raise MXNetError("PagedArena needs at least one kv spec")
        self.capacity = int(capacity)
        self.block_size = int(block_size)
        self.blocks_total = int(blocks_total)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self._ctx = ctx or current_context()
        self.specs = [{"name": s["name"],
                       "shape": tuple(int(d) for d in s["shape"]),
                       "dtype": str(dtype or s.get("dtype", "float32"))}
                      for s in kv_specs]
        rows = self.blocks_total * self.block_size
        dev = self._ctx.jax_device
        with _diag.alloc_origin("decode_kv"):
            self._arrays = [
                jax.device_put(jnp.zeros((rows,) + s["shape"],
                                         dtype=s["dtype"]), dev)
                for s in self.specs
            ]
        #: device bytes ONE block holds across every leaf — the ledger
        #: accounting quantum (live blocks × block_bytes, exact)
        self.block_bytes = sum(
            a.nbytes // self.blocks_total for a in self._arrays)
        self._mem_slot = _diag.ledger().slot(self, 0, "decode_kv",
                                             ctx=str(self._ctx))
        self._free_slots = list(range(self.capacity - 1, -1, -1))
        self._free_blocks = list(range(self.blocks_total - 1, -1, -1))
        self._tables = [None] * self.capacity   # slot -> [block ids]
        self._lock = _conc.lock("PagedArena", "_lock")
        self._view_fns = {}
        self._row_fns = {}
        self._scatter_fns = {}
        self._closed = False

    # ---------------------------------------------------------- slots
    @property
    def free_slots(self):
        with self._lock:
            return len(self._free_slots)

    @property
    def occupancy(self):
        with self._lock:
            return 1.0 - len(self._free_slots) / self.capacity

    @property
    def blocks_free(self):
        with self._lock:
            return len(self._free_blocks)

    @property
    def blocks_live(self):
        with self._lock:
            return self.blocks_total - len(self._free_blocks)

    @property
    def block_occupancy(self):
        """Live-block fraction (the ``decode_kv_blocks_live`` basis)."""
        with self._lock:
            return 1.0 - len(self._free_blocks) / self.blocks_total

    def allocate(self):
        """Claim a free sequence slot (empty block table), or None when
        the arena is full. Blocks are NOT reserved here — the first
        ``ensure_tokens`` call pulls them as the sequence needs them."""
        with self._lock:
            if self._closed or not self._free_slots:
                return None
            slot = self._free_slots.pop()
            self._tables[slot] = []
            return slot

    def release(self, slot):
        """Return ``slot`` AND every block in its table to the free
        pools (sequence finished/evicted/failed). This is the single
        release seam the chaos gate leans on: any eviction path that
        reaches it — including the ``finally`` under an injected
        prefill/alloc fault — leaves the free lists exact."""
        slot = int(slot)
        if not 0 <= slot < self.capacity:
            raise MXNetError("release: slot %d out of range [0, %d)"
                             % (slot, self.capacity))
        with self._lock:
            if self._tables[slot] is None:
                raise MXNetError("release: slot %d is already free" % slot)
            self._free_blocks.extend(reversed(self._tables[slot]))
            self._tables[slot] = None
            self._free_slots.append(slot)
            live = self.blocks_total - len(self._free_blocks)
        self._mem_slot.set(live * self.block_bytes)

    def ensure_tokens(self, slot, n_tokens):
        """Grow ``slot``'s block table until it covers ``n_tokens``
        positions. Host bookkeeping only. Raises :class:`MXNetError`
        when the sequence would exceed ``max_blocks_per_seq`` or the
        shared pool is dry — the caller fails THAT sequence (releasing
        its table) and the pool stays exact. Returns the number of
        blocks newly appended (0 when the table already covered the
        positions) — the session's flight/timeline events record only
        ACTUAL growth, not every covering check."""
        import math
        need = math.ceil(int(n_tokens) / self.block_size)
        with self._lock:
            table = self._tables[slot]
            if table is None:
                raise MXNetError("ensure_tokens: slot %d is free" % slot)
            if need > self.max_blocks_per_seq:
                raise MXNetError(
                    "sequence needs %d KV blocks, over max_blocks_per_seq"
                    " %d (%d tokens at block_size %d)"
                    % (need, self.max_blocks_per_seq, n_tokens,
                       self.block_size))
            grew = 0
            while len(table) < need:
                if not self._free_blocks:
                    raise MXNetError(
                        "KV block pool exhausted (%d blocks live, %d "
                        "needed for slot %d)"
                        % (self.blocks_total, need, slot))
                table.append(self._free_blocks.pop())
                grew += 1
            live = self.blocks_total - len(self._free_blocks)
        self._mem_slot.set(live * self.block_bytes)
        return grew

    def tokens_capacity(self, slot):
        """Token positions ``slot``'s current table covers."""
        with self._lock:
            table = self._tables[slot]
            return len(table) * self.block_size if table else 0

    # ------------------------------------------------------ host indexing
    @property
    def pad_flat_index(self):
        """Out-of-bounds flat row index for padding (scatter drops it;
        row-gather clips it under a fresh mask)."""
        return self.blocks_total * self.block_size

    def flat_index(self, slot, pos):
        """Flat storage row of token position ``pos`` in ``slot``
        (``table[pos // block] · block + pos % block``). The position
        must already be covered by ``ensure_tokens``."""
        pos = int(pos)
        with self._lock:
            table = self._tables[slot]
            if table is None or pos // self.block_size >= len(table):
                raise MXNetError(
                    "flat_index: position %d not covered by slot %d's "
                    "table" % (pos, slot))
            return table[pos // self.block_size] * self.block_size \
                + pos % self.block_size

    def block_table(self, slots):
        """``(len(slots), max_blocks)`` int32 table for ``gather_view``:
        row i holds slot ``slots[i]``'s block ids, padded (and whole
        rows for ``None`` entries) with the out-of-range id
        ``blocks_total``."""
        # mxtpu: allow-sync(host-born block ids — index assembly, never
        # device data)
        out = _np.full((len(slots), self.max_blocks_per_seq),
                       self.blocks_total, dtype=_np.int32)
        with self._lock:
            for i, slot in enumerate(slots):
                if slot is None:
                    continue
                table = self._tables[slot] or []
                out[i, :len(table)] = table
        return out

    # ------------------------------------------------------- device ops
    def _fns(self, bucket, cache, build):
        fn = cache.get(bucket)
        if fn is None:
            fn = build(bucket)
            cache[bucket] = fn
        return fn

    def _build_view(self, bucket):
        nblk, bs = self.blocks_total, self.block_size

        def _view(arrays, tables):
            # (B, max_blocks) block ids -> (B, max_blocks, block, ...)
            # views. mode="clip": table padding carries the out-of-range
            # id blocks_total and clips to the LAST pool block — garbage
            # by design; the step model's attention mask keeps every
            # padded tail block provably inert (select, not multiply)
            return [jnp.take(a.reshape((nblk, bs) + a.shape[1:]),
                             tables, axis=0, mode="clip")
                    for a in arrays]

        return _pipeline.record_program_build(
            "decode_paged", "decode_paged_view[b=%d]" % bucket,
            _pipeline.named_jit("arena_view", _view))

    def _build_rows(self, bucket):
        def _rows(arrays, idx, fresh):
            out = []
            for a in arrays:
                g = jnp.take(a, idx, axis=0, mode="clip")
                mask = fresh.reshape((-1,) + (1,) * (g.ndim - 1))
                # identical select discipline to SequenceSlotArena's
                # gather: fresh/pad rows become the exact zero begin
                # state (0*NaN == NaN would poison slot reuse)
                out.append(jnp.where(mask > 0,
                                     jnp.zeros((), dtype=g.dtype), g))
            return out

        return _pipeline.record_program_build(
            "decode_paged", "decode_paged_rows[b=%d]" % bucket,
            _pipeline.named_jit("arena_rows", _rows))

    def _build_scatter(self, bucket):
        def _scatter(arrays, idx, rows):
            # mode="drop": padding rows carry the out-of-bounds flat
            # index and vanish; donated buffers update in place
            return [a.at[idx].set(r.astype(a.dtype), mode="drop")
                    for a, r in zip(arrays, rows)]

        return _pipeline.record_program_build(
            "decode_paged", "decode_paged_scatter[b=%d]" % bucket,
            _pipeline.named_jit("arena_scatter", _scatter, donate_argnums=0))

    def gather_view(self, slots):
        """Assemble the bucketed ``(B, max_blocks, block, …)`` KV view
        for the step/prefill program — one device gather per leaf, no
        host transfer. ``slots`` may contain ``None`` padding (those
        rows view clipped garbage; the model's mask zeroes their every
        score)."""
        tables = self.block_table(slots)
        fn = self._fns(len(slots), self._view_fns, self._build_view)
        return fn(self._arrays, tables)

    def gather_rows(self, flat_idx, fresh):
        """Pull single token rows by flat position into ``(bucket, …)``
        arrays, zeroing rows flagged fresh (and padding rows, which
        carry the clipped OOB index AND a fresh flag) — the recurrent-
        state compatibility path, byte-identical math to
        :meth:`SequenceSlotArena.gather`."""
        # mxtpu: allow-sync(host-born flat indices/mask — index
        # normalization, not a transfer)
        idx = _np.asarray(flat_idx, dtype=_np.int32)
        # mxtpu: allow-sync(host-born fresh mask — same normalization)
        mask = _np.asarray(fresh, dtype=_np.float32)
        fn = self._fns(len(idx), self._row_fns, self._build_rows)
        return fn(self._arrays, idx, mask)

    def scatter_rows(self, flat_idx, rows):
        """Write one token row per leaf at each flat position; padding
        positions (``pad_flat_index``) are dropped. Donates the old
        buffers — single-consumer by contract (the session's worker)."""
        # mxtpu: allow-sync(host-born flat indices — index normalization)
        idx = _np.asarray(flat_idx, dtype=_np.int32)
        fn = self._fns(len(idx), self._scatter_fns, self._build_scatter)
        self._arrays = fn(self._arrays, idx, list(rows))

    # ------------------------------------------------------- accounting
    def live_kv_bytes(self):
        """The ledger's ``decode_kv`` basis: blocks_live × block_bytes."""
        return self.blocks_live * self.block_bytes

    def state_bytes(self):
        """Physical device bytes of the preallocated pool."""
        return sum(a.nbytes for a in self._arrays) \
            if self._arrays else 0

    def close(self):
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._arrays = None
            self._free_slots = []
            self._free_blocks = []
            self._tables = [None] * self.capacity
        self._mem_slot.set(0)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
