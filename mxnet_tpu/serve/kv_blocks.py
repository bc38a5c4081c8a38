"""Paged KV block allocator: one device-resident page pool per layer.

The vLLM-style substrate under continuous batching
(``serve.scheduler``): instead of per-(batch-bucket) contiguous KV
rings sized ``B x max_seq`` whether or not anyone uses them, every
layer's K/V storage is ONE pool of fixed-width pages —
``(P, KV, page, D)`` rings, plus ``(P, KV, page)`` f32 scale pools on
the int8 rung (PR-10 quantize-on-write composes unchanged: the pages
just hold int8 payloads and their scale rows). A per-slot *page table*
maps each slot's logical ring onto the pages it owns; each step gathers
the table into a contiguous ring, runs the unchanged model cache path,
and scatters the freshly-written rows back — both directions exact
copies (``ops.nn.paged_kv_gather`` / ``paged_kv_scatter``). Fast rungs
fuse the brackets into the step executable
(``serve.generate._CacheForward(paged=True)``) or, in the continuous
engine, drop them: its step consumes the pool arrays (donated), writes
new rows into their pages in place and reads the pages through the table
in the kernel (``_CacheForward(inplace=True)``); the strict baseline
rung instead runs them as standalone eager device ops around the
UNCHANGED ring executable, so its bitwise decode contract survives
paging by construction (in-graph, XLA partitions the attention loops
differently when they read a gather output vs an entry parameter, which
drifts ulps).

Page id 0 is the reserved **null page**: dead/idle slots of a
fixed-width decode step point every table entry at it, their writes
land there, and the scatter op re-zeros it each step — so one compiled
executable serves every occupancy without masking inputs per slot.

The allocator itself is host-side and O(1): a LIFO free list of page
ids. ``assign()`` reserves a slot's whole token budget up front
(prompt + max_new rounded up to pages) so a request can never die
mid-decode from pool pressure — exhaustion surfaces exactly once, at
the admission boundary, as :class:`~.engine.PoolExhausted` (503), and
the scheduler's answer is to requeue, never to crash. ``release()``
recycles the pages the moment a request retires — the memory win over
bucket rings: a slot holds ``ceil((prompt+max_new)/page)`` pages, not
``max_seq``, and holds them only while the request is live.

Every allocated page carries a **reference count** so pages can be
shared across owners (PR-14 prefix caching, ``serve.prefix_cache``):
``assign_with_prefix()`` installs already-written prefix pages at the
front of a slot's table row and bumps their refcounts instead of
copying them, ``incref()`` lets the prefix trie adopt a retiring
request's prompt pages, and ``release()``/``decref()`` *decrement* —
a page returns to the free list only when its last reference drops.
Sharing is copy-on-extend at page granularity: shared pages are
read-only by construction (``paged_kv_scatter`` only writes rows at
``start_pos + [0, t_len)``, and a prefix-hit request's first write
position starts past the shared boundary), so the first divergent
token always lands in a slot-private page and no copy is ever needed.

**Latent pages.** A layer that keeps one array a position and no K/V
pair (``LayerCache.latent``: latent attention's normalised latent and
shared rotated key side by side) has one pool, ``(P, 1, page, D)``, where
another layer has two. It is indexed by the same table and the same page
ids as the K/V pools, so the allocator, the reference counts and the
prefix cache's shared pages know nothing of the kind; float32 alone.

**Two kinds of pages.** A layer whose keys are bounded by a window
(``LayerCache.window``) never needs more than a window of positions and
the page being written, so its K/V does not grow with the sequence. Such
layers share a second, narrower table, the *ring table* of
``ceil(window / page) + 1`` columns a slot, and each has pools of
``num_slots * columns + 1`` pages of its own (page 0 the null page, as
above). Logical page ``j`` of a sequence lies in column ``j mod columns``:
the page that position ``t`` is written to takes the place of the one
``columns`` pages before it, every position of which is out of the window
of ``t`` and of every later query, and not before. A slot is given
``min(pages_for(budget), columns)`` ring pages with its other pages at
admission and hands them back at release; the budget of the ring pools is
fixed, so they are never what admission waits for. The prefix cache never
sees them (``ContinuousEngine`` refuses it for such a model): a ring page
is rewritten while its slot lives.

Page size defaults to the Pallas decode kernel's natural block
(``ops.pallas.decode_attention.natural_block()`` = 128, clamped to
``max_seq``), so the kernel's block-skip masking skips whole unreached
pages; ``MXNET_SERVE_KV_PAGE_SIZE`` / ``MXNET_SERVE_KV_PAGES``
override (CPU tests run 16-wide pages).
"""
from __future__ import annotations

import functools
import threading

import numpy as _onp

from ..base import MXNetError
from .engine import PoolExhausted, block_context
from .generate import CacheLayout


def resolve_page_size(page_size, max_seq):
    """The pool's page width: an explicit argument wins, then
    ``MXNET_SERVE_KV_PAGE_SIZE``, then the decode kernel's natural block
    clamped to ``max_seq``. ``max_seq`` must divide into whole pages —
    the gathered ring must have exactly the contiguous ring's S extent
    or the paged executables would compile different shapes than the
    ring ones (and the bitwise parity contract would be vacuous)."""
    from .. import config

    ps = page_size
    if ps is None:
        ps = int(config.get("MXNET_SERVE_KV_PAGE_SIZE"))
    if ps <= 0:
        from ..ops.pallas.decode_attention import natural_block

        ps = min(natural_block(), int(max_seq))
    ps = int(ps)
    if int(max_seq) % ps:
        raise MXNetError(
            f"max_seq ({max_seq}) must be a multiple of the KV page size "
            f"({ps}); pick a page size that divides it "
            "(MXNET_SERVE_KV_PAGE_SIZE or the page_size argument)")
    return ps


class PagedKVPool:
    """Device page pools + host free-list allocator + per-slot page tables.

    Parameters
    ----------
    model : block with a ``cache_spec()``: the same description
        (:class:`~.generate.CacheLayout`) that
        :meth:`~.generate.KVCache.alloc` builds rings from. K/V is paged;
        a layer's recurrent state, fixed in size whatever the sequence's
        length, is one row a slot beside the pages (``num_slots`` rows,
        never paged, no null row: the step keeps a dead lane's row as it
        is and zeroes a row itself when its request starts, see the
        recurrent-state ops of ``ops/nn.py``).
    num_slots : fixed decode width — page-table rows (the trace-static
        slot lattice of the continuous-batching step).
    max_seq : logical ring length per slot (page table width =
        ``max_seq // page_size``).
    page_size : page width in tokens; ``None`` resolves via
        :func:`resolve_page_size`.
    num_pages : pool capacity in pages **including** the reserved null
        page; ``None`` resolves ``MXNET_SERVE_KV_PAGES``, whose 0
        default auto-sizes to full capacity
        (``num_slots * pages_per_slot + 1`` — exhaustion impossible).
        Size it smaller to oversubscribe: admission then queues on
        :class:`~.engine.PoolExhausted` until retirements recycle pages.
    quant : ``None`` (f32 pools) or ``"int8"`` (int8 ring pools + f32
        scale pools — PR-10's quantize-on-write flavor).
    """

    def __init__(self, model, num_slots, max_seq, page_size=None,
                 num_pages=None, quant=None):
        from .. import config

        self.num_slots = int(num_slots)
        self.max_seq = int(max_seq)
        self.page_size = resolve_page_size(page_size, self.max_seq)
        self.pages_per_slot = self.max_seq // self.page_size
        if num_pages is None:
            num_pages = int(config.get("MXNET_SERVE_KV_PAGES"))
        if num_pages <= 0:
            num_pages = self.num_slots * self.pages_per_slot + 1
        self.num_pages = int(num_pages)
        if self.num_pages < 2:
            raise MXNetError(
                f"PagedKVPool needs >= 2 pages (1 null + 1 usable), got "
                f"{self.num_pages}")
        self.layout = CacheLayout(model, quant)
        self.quant = quant
        # the ring pages of the layers bounded by a window: a table of
        # their own, a fixed budget (every slot's whole ring), a free list
        self.window_columns = (self.layout.window_columns(self.page_size)
                               if self.layout.window is not None else 0)
        self.window_pages = self.num_slots * self.window_columns + 1
        # a layer at a time, in the layout's flat order (KVCache.flat()'s
        # too, so _CacheForward's calling convention is shared between
        # ring and paged steps): (P, KV, page, D) k/v pools, with
        # (P, KV, page) f32 scale pools on the int8 rung, then the
        # layer's (num_slots, ...) state rows
        self.ctx = block_context(model)
        self.reallocate()
        # host allocator state: LIFO free list (hot pages recycle first),
        # per-slot owned pages, the canonical page-table matrix
        self._lock = threading.Lock()
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._owned = [[] for _ in range(self.num_slots)]
        self._refs = {}  # page id -> reference count (allocated pages)
        self._table = _onp.zeros((self.num_slots, self.pages_per_slot),
                                 _onp.int32)
        self._table_nd = None
        self._wfree = list(range(self.window_pages - 1, 0, -1))
        self._wowned = [[] for _ in range(self.num_slots)]
        self._wtable = _onp.zeros((self.num_slots, self.window_columns),
                                  _onp.int32)
        self.high_water = 0
        self.exhausted_count = 0

    # -- executable calling convention --------------------------------------
    def flat(self):
        """The pool arrays in the step executable's calling convention
        (interleaved per layer, like ``KVCache.flat()``)."""
        return list(self._arrays)

    def update_from_flat(self, arrays):
        """Rebind the pool state to the executable's returned arrays.
        In-place by design: pool state is the *persistent* serving
        substrate (unlike per-request ring caches), and every slot's
        live data rides in it between steps."""
        arrays = list(arrays)
        if len(arrays) != len(self._arrays):
            raise MXNetError(
                f"pool update: got {len(arrays)} arrays, expected "
                f"{len(self._arrays)}")
        self._arrays = arrays

    def lost(self):
        """True if a device buffer of the pool is gone: a step that
        consumes its cache arguments (``_CacheForward(inplace=True)``)
        was dispatched and its outputs never came back."""
        return any(a._data.is_deleted() for a in self._arrays)

    def reallocate(self):
        """Zeroed device arrays in place of whatever the pool held (its
        first ones; new ones after :meth:`lost`). The host allocator's
        state is the caller's to settle: every page's content is gone."""
        from .. import numpy as mnp

        self._arrays = self.layout.alloc(
            functools.partial(mnp.zeros, ctx=self.ctx), self.num_pages,
            self.page_size, self.num_slots, window_lead=self.window_pages)

    def table(self):
        """Copy of the canonical (num_slots, pages_per_slot) int32 page
        table. Rows of released slots are all-null (0)."""
        with self._lock:
            return self._table.copy()

    def window_table(self):
        """Copy of the (num_slots, window_columns) int32 ring table of
        the layers bounded by a window (no column for a model that has
        none). Column ``j mod window_columns`` of a row holds the
        sequence's logical page ``j``."""
        with self._lock:
            return self._wtable.copy()

    def table_nd(self):
        """The canonical page table as a cached device NDArray — for
        callers whose table never changes between calls (the
        fully-assigned Generator paged mode). Invalidated by
        assign/release."""
        from .. import numpy as mnp

        with self._lock:
            if self._table_nd is None:
                self._table_nd = mnp.array(self._table, ctx=self.ctx)
            return self._table_nd

    # -- allocator -----------------------------------------------------------
    def pages_for(self, n_tokens):
        """Pages needed to hold ``n_tokens`` ring positions."""
        n = int(n_tokens)
        return max(1, -(-n // self.page_size))

    def slot_budget(self, slot):
        """Token positions ``slot``'s assigned pages can hold (pages
        owned x page_size); 0 for an unassigned slot. The multi-step
        super-step's scatter bracket writes a static-length block of
        ``N`` rows per slot starting at its current position — safe
        because (a) a lane's budget is reserved up front from prompt +
        max_new, and the device loop freezes the lane at its remaining
        budget, so every *advancing* write stays inside this bound; and
        (b) block rows past the lane's write extent scatter back the
        bytes the bracket gathered (a no-op), while positions past the
        table row's last page clip to the null page, which the scatter
        re-zeroes. No page-table view wider than the slot's own row is
        ever needed for an N-token write."""
        with self._lock:
            return len(self._owned[int(slot)]) * self.page_size

    def assign(self, slot, n_tokens):
        """Reserve ``pages_for(n_tokens)`` pages for ``slot`` and install
        them in its page-table row (remaining row entries stay null).
        Raises :class:`PoolExhausted` — atomically, nothing allocated —
        when the free list is short; raises :class:`MXNetError` on a
        slot that already owns pages (the scheduler must release first).
        Returns the number of pages assigned."""
        return self.assign_with_prefix(slot, n_tokens, ())

    def assign_with_prefix(self, slot, n_tokens, prefix_pages):
        """Like :meth:`assign`, but the slot's table row *starts* with
        ``prefix_pages`` — already-written pages (a prefix-trie match)
        whose refcounts are bumped instead of allocating + rewriting
        them. Only ``pages_for(n_tokens) - len(prefix_pages)`` fresh
        pages come off the free list; exhaustion is still atomic
        (nothing increffed, nothing allocated). Shared pages are
        read-only for this slot by the copy-on-extend contract: its
        first write position is at/after the shared-token boundary, so
        every write lands in one of the slot-private pages."""
        slot = int(slot)
        need = self.pages_for(n_tokens)
        shared = [int(p) for p in prefix_pages]
        if n_tokens > self.max_seq:
            raise MXNetError(
                f"slot budget {n_tokens} exceeds max_seq {self.max_seq}")
        if shared and len(shared) >= need:
            raise MXNetError(
                f"prefix ({len(shared)} pages) must leave >= 1 private "
                f"page in a {need}-page budget (the divergent token "
                "needs somewhere to land)")
        fresh_need = need - len(shared)
        with self._lock:
            if self._owned[slot]:
                raise MXNetError(
                    f"slot {slot} already owns {len(self._owned[slot])} "
                    "pages; release() before re-assigning")
            if any(self._refs.get(p, 0) < 1 for p in shared):
                raise MXNetError(
                    f"prefix pages {shared} are not all live (evicted "
                    "between match and assign?)")
            if fresh_need > len(self._free):
                self.exhausted_count += 1
                err = PoolExhausted(
                    f"KV page pool exhausted: need {fresh_need} pages, "
                    f"{len(self._free)} free of {self.num_pages - 1}")
                # backpressure hint: pages free as requests retire; one
                # slot's worth of decode is the natural retry horizon
                err.retry_after_ms = 50.0
                raise err
            fresh = [self._free.pop() for _ in range(fresh_need)]
            for p in shared:
                self._refs[p] += 1
            for p in fresh:
                self._refs[p] = 1
            pages = shared + fresh
            self._owned[slot] = pages
            self._table[slot] = 0
            self._table[slot, :need] = pages
            if self.window_columns:
                # the slot's ring: never short (the budget is every
                # slot's whole ring), never shared
                ring = [self._wfree.pop()
                        for _ in range(min(need, self.window_columns))]
                self._wowned[slot] = ring
                self._wtable[slot] = 0
                self._wtable[slot, :len(ring)] = ring
            self._table_nd = None
            used = self.pages_used
            if used > self.high_water:
                self.high_water = used
            return need

    def release(self, slot):
        """Drop ``slot``'s reference on every page it holds and null its
        table row; pages whose refcount reaches zero recycle to the free
        list (pages the prefix trie still references survive).
        Idempotent (releasing an empty slot is a no-op). The pages'
        device contents are left stale on purpose: the attention
        position mask plus prefill's exact overwrite make stale pages
        unreadable before they are rewritten, so retirement costs zero
        device work."""
        slot = int(slot)
        with self._lock:
            pages, self._owned[slot] = self._owned[slot], []
            if not pages:
                return 0
            if len(set(pages)) != len(pages) or 0 in pages:
                raise MXNetError(
                    f"corrupt page ownership for slot {slot}: {pages}")
            self._decref_locked(pages)
            self._table[slot] = 0
            self._table_nd = None
            ring, self._wowned[slot] = self._wowned[slot], []
            self._wfree.extend(reversed(ring))
            self._wtable[slot] = 0
            return len(pages)

    # -- reference counting (prefix-cache sharing) ---------------------------
    def _decref_locked(self, pages):
        freed = []
        for p in pages:
            n = self._refs.get(p, 0) - 1
            if n > 0:
                self._refs[p] = n
            elif n == 0:
                del self._refs[p]
                freed.append(p)
            else:
                raise MXNetError(f"decref of free page {p}")
        self._free.extend(reversed(freed))
        return freed

    def incref(self, pages):
        """Add one reference to each of ``pages`` (the prefix trie
        adopting a retiring slot's prompt pages). Pages must be live."""
        pages = [int(p) for p in pages]
        with self._lock:
            for p in pages:
                if self._refs.get(p, 0) < 1:
                    raise MXNetError(f"incref of free page {p}")
            for p in pages:
                self._refs[p] += 1

    def decref(self, pages):
        """Drop one reference from each of ``pages``; returns the pages
        that reached zero and recycled to the free list (the prefix
        trie's eviction path)."""
        with self._lock:
            return self._decref_locked([int(p) for p in pages])

    def refcount(self, page):
        """Current reference count of ``page`` (0 = free)."""
        with self._lock:
            return self._refs.get(int(page), 0)

    @property
    def pages_shared(self):
        """Pages currently held by more than one reference (a live slot
        plus the trie, or several slots on one prefix)."""
        with self._lock:
            return sum(1 for n in self._refs.values() if n > 1)

    # -- readout -------------------------------------------------------------
    @property
    def pages_total(self):
        """Usable pages (the null page is bookkeeping, not capacity)."""
        return self.num_pages - 1

    @property
    def pages_free(self):
        with self._lock:
            return len(self._free)

    @property
    def pages_used(self):
        return self.pages_total - len(self._free)

    def nbytes(self):
        """Bytes of everything the pool holds on the device: the pages
        and the state rows."""
        return sum(int(_onp.prod(a.shape)) * _onp.dtype(a.dtype).itemsize
                   for a in self._arrays)

    def state_nbytes(self):
        """The state rows' part of :meth:`nbytes` (0 for a model that
        keeps K/V alone)."""
        return self.layout.state_nbytes(self._arrays)

    def window_nbytes(self):
        """The ring pools' part of :meth:`nbytes`: the K/V of the layers
        bounded by a window (0 for a model that has none)."""
        return sum(int(_onp.prod(a.shape)) * _onp.dtype(a.dtype).itemsize
                   for a, w in zip(self._arrays, self.layout.windows)
                   if w is not None)

    def latent_nbytes(self):
        """The latent pools' part of :meth:`nbytes`: the one array a
        position of the layers that keep no K/V pair (0 for a model that
        has none)."""
        return self.layout.kind_nbytes(self._arrays, "latent")

    def latent_bytes_per_position(self):
        """Bytes one position takes in the latent pools, all such layers
        together."""
        return self.latent_nbytes() // (self.num_pages * self.page_size)

    def stats(self):
        with self._lock:
            free = len(self._free)
            owned = sum(len(o) for o in self._owned)
            shared = sum(1 for n in self._refs.values() if n > 1)
        return {"page_size": self.page_size,
                "pages_total": self.pages_total,
                "pages_free": free,
                "pages_used": self.pages_total - free,
                "pages_owned": owned,
                "pages_shared": shared,
                "high_water": self.high_water,
                "exhausted_count": self.exhausted_count,
                "nbytes": self.nbytes(),
                "state_nbytes": self.state_nbytes(),
                "window_nbytes": self.window_nbytes(),
                "latent_nbytes": self.latent_nbytes(),
                "latent_bytes_per_position": self.latent_bytes_per_position(),
                "window_columns": self.window_columns,
                "window_pages_used": self.window_pages - 1
                - len(self._wfree)}
