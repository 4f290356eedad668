"""Paged KV-cache allocator: fixed-size blocks, block tables, free-list,
per-page refcounts.

The device-side page pools (``[num_pages, page_size, K_kv * D]`` per
layer: a token's KV heads side by side, so that with ``K_kv * D`` a
multiple of 128 the chip keeps them row-major with no lane padding,
the layout the programs' scatters and the paged kernel address; owned
by the serving engine and donated through every decode step) are dumb
storage; THIS object is the authority over which
physical page belongs to whom.  Design follows the vLLM/"Ragged Paged
Attention" memory model (PAPERS.md, arXiv 2604.15464):

- **fixed-size blocks** — a sequence of length L owns
  ``ceil(L / page_size)`` pages; internal fragmentation is bounded by
  one partial page per sequence instead of ``max_len - L`` slots of a
  dense cache;
- **free-list reuse** — released pages go back LIFO, so a churning
  workload keeps re-touching the same hot pages;
- **reservation-based admission** — a request is admitted only when
  pages for its WORST CASE (prompt + max_new_tokens) are free, reserved
  up front.  Decode can then never OOM mid-flight: admission is the
  single choke point, and a rejected request waits in the queue instead
  of killing resident sequences (OOM-aware admission, ISSUE 9);
- **per-page refcounts** (ISSUE 15) — a physical page can back the SAME
  token history for many sequences at once (refcounted prefix caching:
  the prompt pages of a system-prompt-heavy workload are shared, not
  re-stored).  ``allocate`` hands pages out at refcount 1, ``retain``
  adds a reference, ``release`` drops one and only a page's LAST
  release returns it to the free list.  Shared pages are read-only by
  convention: the scheduler routes every write to pages whose refcount
  is 1 (freshly-allocated suffix / copy-on-write pages), so sharing can
  never corrupt another sequence's history.

**Page 0 is reserved as the scratch page**: inactive serving slots and
prompt padding scatter their K/V writes there, and no in-range block-
table entry ever points at it — that is what makes slot join/leave
invisible (bit-exact) to resident slots.  The allocator simply never
hands page 0 out.

Pure host-side bookkeeping (ints); nothing here touches jax.
"""
from __future__ import annotations

from ..base import MXNetError

__all__ = ["PagedKVAllocator", "normalize_kv_dtype"]

#: physical page id every masked/inactive write is routed to
SCRATCH_PAGE = 0

#: kv_dtype mode -> (payload bytes per K/V value, fp32 scale rows per
#: page per pool).  fp32 is the bit-identical default; bf16 halves the
#: payload with no auxiliary state; int8 (ISSUE 20) quarters it and
#: carries one fp32 absmax scale per page per KV head per pool.
_KV_DTYPES = {"fp32": (4, 0), "bf16": (2, 0), "int8": (1, 1)}
_KV_ALIASES = {"float32": "fp32", "bfloat16": "bf16"}


def normalize_kv_dtype(kv_dtype):
    """Canonical kv_dtype name (``fp32`` / ``bf16`` / ``int8``); None
    and '' mean the fp32 default.  Raises on anything else — a typo'd
    env var must not silently serve full-precision pools."""
    s = str(kv_dtype or "fp32").strip().lower()
    s = _KV_ALIASES.get(s, s)
    if s not in _KV_DTYPES:
        raise ValueError(
            "unknown kv_dtype %r (want one of %s)"
            % (kv_dtype, "/".join(sorted(_KV_DTYPES))))
    return s


class PagedKVAllocator:
    def __init__(self, num_pages, page_size, kv_dtype=None,
                 slot_state_bytes=0):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "reserved scratch page)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        #: storage mode of the pools this allocator governs (ISSUE 20).
        #: The allocator itself stays pure page bookkeeping — the mode
        #: only parameterizes the byte-sizing helpers below, so
        #: capacity math (scheduler reservations, serve_report, bench)
        #: has ONE authority for what a page costs.
        self.kv_dtype = normalize_kv_dtype(kv_dtype)
        #: bytes of per-slot recurrent state (all layers) a resident
        #: sequence holds beside its pages; 0 for a model without such
        #: layers.  Slots are the scheduler's to hand out; the byte
        #: count lives here so that capacity has one authority.
        self.slot_state_bytes = int(slot_state_bytes)
        # LIFO free list, scratch page excluded.  Reversed so the first
        # allocations hand out low page ids (stable, test-friendly).
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._refs = {}          # page id -> refcount (>= 1)
        # pages whose ONLY readable content is speculative draft K/V
        # (ISSUE 16): marked by the engine around each spec-decode
        # dispatch, cleared when the step's acceptance commits.  A page
        # released while still marked is a rollback leak — caught at
        # release time, not as a slow pool bleed.
        self._spec = set()

    # -- sizing ------------------------------------------------------------
    @property
    def kv_itemsize(self):
        """Payload bytes per stored K/V value under this kv_dtype."""
        return _KV_DTYPES[self.kv_dtype][0]

    def page_bytes(self, kv_heads, head_dim):
        """Bytes ONE physical page costs in ONE layer: K + V payload
        rows plus (int8 mode) the two per-page-per-KV-head fp32 scale
        rows.  The worst-case reservation of a request is therefore
        ``pages_for(prompt + max_new) * page_bytes(...) * n_layers``
        (SERVING.md §2d) — quantization shrinks the BYTES, never the
        page count, so every page-granular invariant (conservation,
        refcounts, speculative marks) is dtype-blind."""
        item, scale_rows = _KV_DTYPES[self.kv_dtype]
        b = 2 * self.page_size * int(kv_heads) * int(head_dim) * item
        return b + 2 * scale_rows * int(kv_heads) * 4

    def latent_page_bytes(self, width):
        """Bytes ONE physical page of a LATENT pool costs in one layer:
        ``page_size`` rows of ``width`` values that serve as key and
        value at once (never int8: the engine refuses that)."""
        return self.page_size * int(width) * self.kv_itemsize

    def state_bytes(self, slots):
        """Bytes of per-slot state ``slots`` resident sequences hold."""
        return int(slots) * self.slot_state_bytes

    def scale_bytes(self, kv_heads):
        """Scale-pool bytes per page (both pools; 0 unless int8)."""
        return 2 * _KV_DTYPES[self.kv_dtype][1] * int(kv_heads) * 4

    def pages_for(self, tokens):
        """Pages a ``tokens``-long sequence occupies (>= 1 so even an
        empty reservation owns its first page)."""
        return max(1, -(-int(tokens) // self.page_size))

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def used_pages(self):
        return len(self._refs)

    @property
    def shared_pages(self):
        """Pages currently referenced more than once (prefix sharing)."""
        return sum(1 for c in self._refs.values() if c > 1)

    def refcount(self, page):
        """Current reference count of ``page`` (0 when free)."""
        return self._refs.get(int(page), 0)

    @property
    def speculative_pages(self):
        """Pages currently marked speculative (draft K/V not yet
        committed by an acceptance decision).  Must be 0 between decode
        steps and at drain — the engine marks before each speculative
        dispatch and clears when the step's acceptance lands."""
        return len(self._spec)

    # -- speculative decoding (ISSUE 16) -----------------------------------
    def mark_speculative(self, pages):
        """Mark allocated pages as holding ONLY speculative draft K/V
        (the pages a spec-decode dispatch writes beyond the slot's
        committed context).  Marking a free/never-allocated page raises:
        a draft write landing in storage nobody owns is page-table
        corruption, not bookkeeping."""
        pages = [int(p) for p in pages]
        for p in pages:
            if p not in self._refs:
                raise MXNetError(
                    "speculative mark on page %d which is not "
                    "allocated (free or scratch/foreign page)" % p)
        self._spec.update(pages)
        return pages

    def clear_speculative(self, pages=None):
        """Commit/rollback the speculative marks (``None`` = all).
        Content-wise there is nothing to undo — rejected draft
        positions sit beyond the committed context, so every later
        read masks them and later tokens overwrite them in place;
        this clears only the accounting."""
        if pages is None:
            n = len(self._spec)
            self._spec.clear()
            return n
        pages = {int(p) for p in pages}
        n = len(self._spec & pages)
        self._spec -= pages
        return n

    # -- admission ---------------------------------------------------------
    def can_reserve(self, n):
        """Would ``allocate(n)`` succeed right now?  The scheduler's
        OOM-aware admission check: a request whose worst case does not
        fit stays queued."""
        return int(n) <= len(self._free)

    def allocate(self, n):
        """Take ``n`` pages off the free list (each at refcount 1).
        Raises MXNetError when the pool cannot satisfy the request —
        callers are expected to have asked :meth:`can_reserve` first
        (the scheduler does), so this raising means an accounting bug,
        not load."""
        n = int(n)
        if n > len(self._free):
            raise MXNetError(
                "paged KV cache OOM: requested %d pages, %d free of %d "
                "(admission should have rejected this request)"
                % (n, len(self._free), self.num_pages - 1))
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def retain(self, pages):
        """Add one reference to each already-allocated page — how a new
        request maps a cached prefix page (or the prefix index pins a
        page) without owning it.  Retaining a free page raises: sharing
        storage nobody owns is a use-after-free in the making."""
        pages = [int(p) for p in pages]
        for p in pages:
            if p not in self._refs:
                raise MXNetError(
                    "retain of page %d which is not allocated (free or "
                    "scratch/foreign page)" % p)
        for p in pages:
            self._refs[p] += 1
        return pages

    def release(self, pages):
        """Drop one reference per page; a page's LAST release returns it
        to the free list (LIFO).  Releases of free/never-allocated ids
        raise — over-release is a use-after-free bug that would silently
        corrupt ANOTHER sequence's history if let through.  A DUPLICATE
        page within one call raises too: no caller legitimately holds
        two references through a single page list, and on a shared page
        (refcount >= 2) the double decrement would silently steal
        another holder's reference — the one double-free class plain
        conservation cannot catch."""
        pages = [int(p) for p in pages]
        if len(set(pages)) != len(pages):
            raise MXNetError(
                "duplicate pages in one release call: %r (a double "
                "free that refcounting would silently absorb)"
                % sorted(pages))
        for p in pages:
            if p not in self._refs:
                raise MXNetError(
                    "release of page %d which is not allocated (double "
                    "free or scratch/foreign page)" % p)
            if self._refs[p] == 1 and p in self._spec:
                # a rollback leak: the engine dispatched drafts into
                # this page and is freeing it without ever committing
                # or rolling back the acceptance — caught HERE, at the
                # release, instead of surfacing later as a freed page
                # whose stale draft K/V another slot inherits
                raise MXNetError(
                    "release of page %d while still marked "
                    "speculative — a draft dispatch was never "
                    "committed or rolled back" % p)
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)

    # -- invariants ----------------------------------------------------------
    def assert_conservation(self):
        """Page conservation: every usable page is in exactly ONE of
        free-list / allocated-map, none twice, scratch in neither, and
        every allocated page carries a POSITIVE refcount.  Raises
        MXNetError naming the violation.  Called by tests and by the
        drain/mass-rejection paths — a request verdict that leaked,
        duplicated, or double-freed a (possibly shared) page would
        corrupt another sequence's history long after the offending
        request is gone."""
        free = list(self._free)
        free_set = set(free)
        if len(free_set) != len(free):
            raise MXNetError("free-list holds duplicate pages: %r" % free)
        if free_set & set(self._refs):
            raise MXNetError(
                "pages both free and allocated: %r"
                % sorted(free_set & set(self._refs)))
        bad = sorted(p for p, c in self._refs.items() if c < 1)
        if bad:
            raise MXNetError(
                "allocated pages with non-positive refcount: %r" % bad)
        if SCRATCH_PAGE in free_set or SCRATCH_PAGE in self._refs:
            raise MXNetError("scratch page leaked into the pool")
        usable = self.num_pages - 1
        if len(free_set) + len(self._refs) != usable:
            raise MXNetError(
                "page conservation violated: %d free + %d allocated != "
                "%d usable" % (len(free_set), len(self._refs), usable))
        # speculative marks (ISSUE 16) live strictly inside the
        # allocated set: a mark on a free page means draft K/V landed
        # in storage nobody owns
        stray = sorted(self._spec - set(self._refs))
        if stray:
            raise MXNetError(
                "speculative marks on non-allocated pages: %r" % stray)
        return True
