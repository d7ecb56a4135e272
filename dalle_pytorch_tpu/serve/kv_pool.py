"""Paged KV-cache subsystem: the block-pool memory manager.

The dense slot pool (serve/engine.py, ``kv="dense"``) reserves
``num_slots × seq_len`` KV rows of HBM per layer up front, so HBM — not
compute — caps serving concurrency: a slot 10 tokens into a 1280-token
sequence holds 1280 rows of memory. Paged KV (PAPERS.md "Ragged Paged
Attention"; the Gemma-on-TPU serving study credits this exact mechanism
for most of its throughput headroom) breaks the cache into fixed-size
PAGES shared by every slot:

  * the device side is a page pool whose entry comes from the model's
    block (``page_layout``, the one place that says what a page of a
    layer holds). In every block a page is ``(page_size, row)``: whole
    rows, a token's numbers side by side, no axis between page and row.
    The classic block's K and V rows hold every head's ``dim_head``
    numbers, head-major: ``(depth, num_pages, page_size, heads *
    dim_head)`` each (the int8 variant carries scale pages
    ``(page_size, heads)``); a latent-attention block has one row a
    token and no V. Built by ``init_page_pool``, plus per-slot block
    tables ``(num_slots, max_pages)`` int32 mapping logical page j →
    physical page id — ``ops.decode.layer_pool_view`` /
    ``_store_entries_paged`` are the decode step's read and write through
    them (``paged_view`` the all-layer dense oracle), and
    ``ops.paged_attention`` is the Pallas kernel that consumes the tables
    in place (``paged_attn='kernel'``, which also imposes the page-size
    tile constraint ``validate_page_size`` gates);
  * the host side is THIS module's ``PageAllocator``: a free-list over
    physical pages. Physical page 0 is reserved as the TRASH page —
    dead slots park their writes there (see ops/decode.py), so it is
    never handed out;
  * the lifecycle is allocate-on-admission for the prompt span, grow by
    one page as ``pos`` crosses a page boundary (the engine maps ahead
    of every fused K-step chunk, so growth never needs a mid-chunk
    host sync), and free-on-completion/expiry/eviction.

Speculative decode (``speculative=k``) changes only the map-ahead
HORIZON, never the lifecycle: the engine provisions ``chunk_steps × k``
rows per chunk (the most a chunk can deliver at full acceptance)
instead of ``chunk_steps``. There is NO allocation churn on rejection —
a rejected draft's K/V rows sit above the slot's committed ``pos`` on
already-mapped pages and are simply overwritten by the next round's
k-wide write before ``pos`` ever crosses them, so pages are never
unmapped, shrunk, or re-requested mid-request; ``pos`` (and therefore
the page high-water mark) only moves forward. A low-acceptance slot
just reaches its map-ahead pages later than the estimate assumed; the
engine tightens the position estimate at every harvest so the horizon
tracks delivered tokens, not drafted ones.

Overcommit is the point: the engine may run more slots than
``num_pages`` could hold at full length, because concurrent requests sit
at ragged positions. When the pool genuinely runs out mid-decode, the
typed ``PagePoolExhausted`` backpressure path EVICTS the lowest-priority
active request back to the queue — pages freed, request re-queued with
its original handle, never dropped — and deterministic sampling replays
its exact tokens on re-admission (docs/SERVING.md "Paged KV").

Module-level imports stay jax-free (the ``serve`` package's lazy-import
discipline): queue-side callers can type-check against
``PagePoolExhausted`` before a backend exists.
"""

from __future__ import annotations

from typing import Dict, List

from dalle_pytorch_tpu.utils.metrics import structured_event

# physical page 0 is reserved: dead slots' parked writes land here, and
# unmapped block-table entries point here (reads of it are never attended)
TRASH_PAGE = 0

# the ragged paged-attention kernel's tile constraints
# (ops/paged_attention.py): a page is the kernel's K-tile, staged whole
# into VMEM, and the gate keeps it to whole f32 sublane tiles (8 rows).
# Mosaic itself compiles every page size from 4 to 32 rows for f32, bf16
# and int8 pools (the block equals the array's last two dims), so one
# gate serves every dtype; parity with the gather oracle is established
# at 8 and 16 rows. The gather path has no floor (any page_size works).
KERNEL_MIN_PAGE_SIZE = 8
KERNEL_PAGE_MULTIPLE = 8


class PageSizeError(ValueError):
    """Typed page-size rejection at pool init: the configured
    ``page_size`` cannot feed the ragged paged-attention kernel
    (``ops/paged_attention.py`` stages one page per grid step as a VMEM
    K-tile, and pages are kept to whole 8-row f32 sublane tiles).
    Raised HERE, with the
    constraint named, instead of failing opaquely inside
    ``pl.pallas_call``. ``record`` is the structured event."""

    def __init__(self, record: dict):
        super().__init__(
            f"page_size={record.get('page_size')} cannot feed the "
            f"ragged paged-attention kernel (ops/paged_attention.py): "
            f"pages are staged whole into VMEM as the kernel's K-tile, "
            f"so page_size must be >= {record.get('min_page_size')} "
            f"(the f32 sublane tile) and a multiple of "
            f"{record.get('page_multiple')}. Use --paged_attn gather "
            f"for arbitrary page sizes.")
        self.record = record


def validate_page_size(page_size: int) -> None:
    """Gate a pool's ``page_size`` against the kernel tile constraints
    — called at pool init when ``paged_attn='kernel'`` is selected (and
    again by the kernel entry itself, so a direct caller cannot reach
    the opaque Mosaic failure either)."""
    ps = int(page_size)
    if ps < KERNEL_MIN_PAGE_SIZE or ps % KERNEL_PAGE_MULTIPLE:
        raise PageSizeError(structured_event(
            "serve_page_size_invalid", page_size=ps,
            min_page_size=KERNEL_MIN_PAGE_SIZE,
            page_multiple=KERNEL_PAGE_MULTIPLE))


class PageReleaseUnderflow(ValueError):
    """Typed refcount underflow: a release of a page whose refcount is
    already zero (it is already on the free list). Under copy-on-write
    sharing this is the same bug class the old double-release guard
    caught — a page freed past its reference count would sit in the
    free list while a sibling's block table still maps it, and the next
    allocation would hand it to a SECOND live slot whose decode writes
    would silently interleave with the sibling's reads. Fail at the
    bug's site. ``record`` is the structured event."""

    def __init__(self, record: dict):
        super().__init__(
            f"double release of page {record.get('page')}: its refcount "
            f"is already 0 (it is already free) — freeing it again "
            f"would let two live slots end up sharing it")
        self.record = record


class PagePoolExhausted(RuntimeError):
    """Typed page backpressure: an allocation the free-list cannot serve.
    ``record`` is the structured event (kind ``serve_page_exhausted``)
    carrying the shortfall — the engine's eviction path catches this and
    converts it into a requeue, never a dropped request or a wedged
    loop."""

    def __init__(self, record: dict):
        super().__init__(
            f"page pool exhausted: need {record.get('pages_needed')}, "
            f"free {record.get('pages_free')} of "
            f"{record.get('pages_capacity')}")
        self.record = record


def pages_for(rows: int, page_size: int) -> int:
    """Pages needed to hold ``rows`` KV rows (ceil division)."""
    return -(-rows // page_size)


def page_layout(cfg, page_size: int, quantized: bool = False) -> dict:
    """What ONE page of ONE layer holds, buffer by buffer: ``{name:
    (shape of a page, bytes an element; None = the pool's float type)}``.
    The one place that knows the entry's format. The pool
    (``init_page_pool``), its modeled bytes (``modeled_kv_bytes``) and
    through them the engine's sizing follow it; ``snapshot_page``,
    ``restore_page`` and ``pool_bytes`` are generic over the buffers, and
    in every buffer a page is one contiguous run whose first page axis is
    ``num_pages`` (``(depth, num_pages) + page shape``).

    A page is ``(page_size, row)`` in every block: whole rows, no axis
    between page and row, so a gathered page is read as it lies and a new
    row is one contiguous run of it.

      * the classic block: one K row and one V row a token, every head's
        ``dim_head`` numbers side by side in it, head-major:
        ``(page_size, heads * dim_head)`` each, the grouped-query page at
        ``kv_heads == heads`` (``quantized``: int8 rows and a float32
        scale a head a row, ``(page_size, heads)``: the accuracy contract
        of ``ops.decode.init_cache``, so int8-KV composes with paging);
      * a latent-attention block (``cfg.block``): ONE row a token, the
        latent and the roped key side by side and filled up to whole
        lanes, ``(page_size, row_width)``: no head axis and no V;
      * a block of grouped-query or differential attention layers, window
        and full: one K row and one V row a token, every KEY/VALUE head's
        numbers side by side in it, ``(page_size, kv_heads * head_dim)``
        each (no head axis between page and row: the read contracts whole
        rows, as the latent block's does), in two pools, one a layer type
        (``pool_plan``): ``k`` / ``v`` hold the full layers, ``window_k``
        / ``window_v`` the window layers. The width is a BUFFER's
        (``DescribedBlock.buffer_row_width``): where a key head is wider
        than a value head, or the layer types differ in key/value heads,
        the four buffers have four widths, and everything below follows
        this table buffer by buffer;
      * a block with recurrent layers holds, beside its page pools, a
        cache that is NOT pages: what a slot carries a layer, fixed in
        size, buffer by buffer as the BLOCK names it
        (``DescribedBlock.state_layout``). A state-space layer's is
        ``ssm_state`` ``(d_state, d_inner)`` (the wide dimension minor:
        whole lanes) in float32 whatever the pool's type, and the
        convolution's tail ``ssm_conv`` ``(d_conv - 1, d_inner)``; a
        gated short convolution's is ONE buffer, ``conv_tail``
        ``(conv_taps - 1, dim)``. The axis that is a pool's ``num_pages``
        is the engine's slots there (``pool_plan``): nothing is allocated
        or freed, a slot's state is overwritten at admission."""
    blk = getattr(cfg, "block", None)
    if blk is not None:
        if quantized:
            from dalle_pytorch_tpu.ops.transformer import BlockOptionError
            raise BlockOptionError(blk.name, "quantize_cache")
        out = {}
        for pool, names in blk.pools(cfg.depth).items():
            if pool == "state":
                out.update(blk.state_layout(cfg.dim))
            else:
                out.update({name: ((page_size, blk.buffer_row_width(name)),
                                   None) for name in names})
        return out
    page = (page_size, cfg.heads * cfg.dim_head)
    if quantized:
        scales = (page_size, cfg.heads)
        return {"k": (page, 1), "v": (page, 1),
                "k_scale": (scales, 4), "v_scale": (scales, 4)}
    return {"k": (page, None), "v": (page, None)}


def pool_plan(cfg, num_pages: int, window_pages: int,
              num_slots: int = 0) -> dict:
    """``{buffer: (layers, pages)}``: how many layers and pages each
    buffer of ``page_layout`` spans. The layers of a buffer are the layers
    that STORE to it, not those that read it: a full pool of one layer may
    be read by many. Every block but the described ones with window layers
    holds ONE page pool, every layer's pages under one page id; those
    hold a pool a layer type, because a window layer never reads more than
    its window of a slot's rows and its pages are reused as the slot moves
    on (``WindowPages``): the full layers' ``k`` / ``v`` with
    ``num_pages`` pages and the window layers' ``window_k`` / ``window_v``
    with ``window_pages``. A recurrent layer's buffers span
    ``num_slots``: a state a slot, and a block that has such layers is
    given no plan without them."""
    blk = getattr(cfg, "block", None)
    if blk is None:
        return {name: (cfg.depth, num_pages)
                for name in ("k", "v", "k_scale", "v_scale")}
    spans = {"full": num_pages, "window": window_pages, "state": num_slots}
    pools = blk.pools(cfg.depth)
    if "state" in pools and num_slots <= 0:
        raise ValueError(
            f"block {blk.name!r} holds a recurrent state a slot: "
            f"num_slots={num_slots} gives its state no room")
    return {name: (len(blk.cache_layers(pool, cfg.depth)), spans[pool])
            for pool, names in pools.items() for name in names}


def window_pool_pages(cfg, num_slots: int, total_len: int, page_size: int,
                      num_pages: int) -> int:
    """Pages of the window pool (trash page included) beside a full pool
    of ``num_pages``: the same share of what every slot could hold at
    once. 0 where the block has no window layers."""
    blk = getattr(cfg, "block", None)
    if blk is None or not blk.cache_layers("window", cfg.depth):
        return 0
    ring = blk.ring_pages(page_size, total_len)
    whole = num_slots * pages_for(total_len, page_size)
    return max(-(-(num_pages - 1) * num_slots * ring // whole), ring) + 1


def init_page_pool(cfg, num_pages: int, page_size: int, dtype=None,
                   quantized: bool = False, window_pages: int = 0,
                   num_slots: int = 0) -> dict:
    """Device-resident page pool(s): one ``(layers, pages) + page shape``
    buffer for each entry of ``page_layout``, spanning what ``pool_plan``
    says (``num_slots`` for a state-space layer's state)."""
    import jax.numpy as jnp
    if dtype is None:
        dtype = jnp.float32
    kinds = {None: dtype, 1: jnp.int8, 4: jnp.float32}
    layout = page_layout(cfg, page_size, quantized)
    plan = pool_plan(cfg, num_pages, window_pages, num_slots)
    return {name: jnp.zeros(plan[name] + shape, kinds[size])
            for name, (shape, size) in layout.items()}


def visible_table_view(block_tables, visible):
    """Visibility-trimmed view of per-slot block tables: row i of the
    result lists the PHYSICAL pages behind slot i's visible logical
    pages — ``visible`` (b, W) int32 is the per-position visible-page
    list ``ops.sparse.visible_pages`` precomputes, indexed at each
    slot's current position (sparsity-aware decode reads,
    docs/SERVING.md "Sparse decode reads"). Entries past the visible
    count mirror whatever the padding entries map (logical page 0);
    consumers must mask those columns — the view narrows the READ, the
    mask still decides attendance. Traced code (jax.numpy), called
    from inside the fused decode program."""
    import jax.numpy as jnp
    return jnp.take_along_axis(block_tables, visible, axis=1)


def snapshot_page(pool: dict, page) -> dict:
    """Device-side copy of ONE physical page across every layer (and the
    int8 pool's scale pages): ``{k: (layers, page_size, row)}``.
    The prefix cache's copy-on-write source — taken at insert time,
    BEFORE the inserting request's decode can write past its prompt
    span into the same physical page. Traced (jax.numpy); the engine
    jits it once per pool layout."""
    return {k: pool[k][:, page] for k in pool}


def restore_page(pool: dict, page, snap: dict) -> dict:
    """Write a ``snapshot_page`` copy into physical page ``page`` — the
    copy-on-write FORK: a warm-hit slot gets a private page whose
    prompt-tail rows are byte-identical to the cached boundary page, so
    its decode appends diverge without ever touching the shared copy.
    Traced; the engine jits it once per pool layout (with the pool's
    shardings pinned on a mesh engine, so the fork can never drift the
    KV store's placement between fused chunks)."""
    return {k: pool[k].at[:, page].set(snap[k]) for k in pool}


def pool_bytes(pool: dict) -> int:
    """Resident HBM bytes of a pool (or of a dense cache dict): one
    number for either layout."""
    return int(sum(x.nbytes for x in pool.values()))


def modeled_kv_bytes(cfg, *, kv: str, num_slots: int, total_len: int,
                     page_size: int = 0, num_pages: int = 0,
                     quantized: bool = False,
                     dtype_bytes: int = 4) -> int:
    """KV-store bytes from CONFIG alone — the same number
    ``pool_bytes`` measures on a live engine's arrays, computable
    without building one (the replica set's /stats for child-process
    engines, whose pools live in another interpreter, and bench's
    HBM-budget math read this). Mirrors the engine's defaults:
    ``page_size`` 0 -> min(16, total_len); ``num_pages`` 0 -> fully
    provisioned (num_slots full sequences + the trash page)."""
    import math
    if kv == "paged":
        ps = int(page_size) or min(16, total_len)
        pages = int(num_pages) or \
            num_slots * pages_for(total_len, ps) + 1
    else:
        # the dense slot cache holds the same rows, a slot a "page"
        ps, pages = total_len, num_slots
    plan = pool_plan(cfg, pages, window_pool_pages(
        cfg, num_slots, total_len, ps, pages), num_slots)
    # a page's bytes, buffer by buffer (quantized: int8 rows plus one
    # f32 scale a row), times the layers and pages the buffer spans
    return int(sum(math.prod(plan[name]) * math.prod(shape)
                   * (size or dtype_bytes) for name, (shape, size) in
                   page_layout(cfg, ps, quantized).items()))


class PageAllocator:
    """Host-side free-list over physical pages ``[1, num_pages)`` (page 0
    is the reserved trash page), REFCOUNTED for copy-on-write sharing
    (docs/SERVING.md 'Prefix cache & per-request CFG'): ``alloc`` hands
    out pages at refcount 1, ``retain`` maps an already-live page into
    another owner's block table (physical sharing — the prefix cache's
    warm hit), and ``release`` decrements, returning a page to the free
    list only when its LAST reference drops. ``in_use`` counts physical
    pages — a page shared by five block tables is one page of HBM —
    which is what keeps /stats' ``pages_in_use`` and the modeled-vs-live
    pool-bytes comparisons exact under sharing. Single-threaded by
    design — the engine owns it under its step lock, like every other
    piece of slot bookkeeping."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (one trash page + at least one "
                f"allocatable), got {num_pages}")
        self.num_pages = int(num_pages)
        # pop() hands out the lowest free id first — deterministic page
        # placement makes failures reproducible
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._free_set = set(self._free)   # O(1) double-release check
        self._refs: Dict[int, int] = {}    # live page -> reference count
        self.peak_in_use = 0
        self.allocs = 0
        self.retains = 0

    @property
    def capacity(self) -> int:
        return self.num_pages - 1          # trash page excluded

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        # PHYSICAL pages: a shared page counts once (refcounts never
        # inflate residency — that is the whole point of sharing)
        return self.capacity - self.free

    @property
    def pages_shared(self) -> int:
        """Physical pages mapped by more than one owner (refcount >= 2)
        — the /stats sharing gauge."""
        return sum(1 for r in self._refs.values() if r >= 2)

    @property
    def refs_saved(self) -> int:
        """Pages of HBM sharing is currently saving: the sum over live
        pages of (refcount - 1) — what a refcount-blind pool would have
        allocated extra."""
        return sum(r - 1 for r in self._refs.values())

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def alloc(self, n: int) -> List[int]:
        """Hand out ``n`` physical page ids at refcount 1, or raise the
        typed ``PagePoolExhausted`` (the caller decides between
        deferring the request and evicting a victim)."""
        if n > self.free:
            raise PagePoolExhausted(structured_event(
                "serve_page_exhausted", pages_needed=int(n),
                pages_free=self.free, pages_in_use=self.in_use,
                pages_capacity=self.capacity))
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        for p in out:
            self._refs[p] = 1
        self.allocs += n
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def retain(self, pages: List[int]) -> None:
        """Add one reference to each (already-live) page — the prefix
        cache's warm hit mapping existing prompt pages into a new
        slot's block table, and the index's own hold on an inserted
        prefix. Retaining a free page is a hard error: its content is
        gone the moment the next ``alloc`` hands it out."""
        for p in pages:
            p = int(p)
            if not 1 <= p < self.num_pages:
                raise ValueError(f"page id {p} was never allocatable")
            if p in self._free_set or p not in self._refs:
                raise ValueError(
                    f"retain of free page {p}: only a live (allocated) "
                    f"page can gain a reference — a free page's content "
                    f"is forfeit to the next alloc")
            self._refs[p] += 1
            self.retains += 1

    def release(self, pages: List[int]) -> None:
        """Drop one reference per page (completion/expiry/eviction/
        prefix-cache eviction); a page returns to the free list only at
        refcount zero — an eviction victim whose pages are still mapped
        by a sibling's block table (or held by the prefix index) must
        NOT hand them to the next allocation. Releasing past zero is
        the typed ``PageReleaseUnderflow``: the refcounted form of the
        double-release guard, failing at the bug's site instead of
        letting two live slots interleave writes in one page."""
        for p in pages:
            p = int(p)
            if not 1 <= p < self.num_pages:
                raise ValueError(f"page id {p} was never allocatable")
            if p in self._free_set or self._refs.get(p, 0) <= 0:
                raise PageReleaseUnderflow(structured_event(
                    "serve_page_release_underflow", page=p,
                    pages_free=self.free, pages_in_use=self.in_use))
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
                self._free_set.add(p)


class WindowPages:
    """Host side of a window pool (``pool_plan``): its own allocator, a
    ring table a slot and each slot's pages. A slot's table has ``ring``
    columns (``WindowGQABlock.ring_pages``: the window's pages and one
    more) and logical page j lies in column ``j % ring``: as ``pos`` moves
    on, the page that has wholly left the window is REUSED in place for
    the page ahead, so a slot never holds more than ``ring`` pages of a
    window layer however long its sequence, and a short one holds only
    what it has reached. Map-ahead, exhaustion (``PagePoolExhausted`` from
    ``alloc``) and the trash page are the full pool's; the engine owns an
    instance under its step lock."""

    def __init__(self, num_slots: int, num_pages: int, ring: int,
                 page_size: int):
        import numpy as np
        self.alloc = PageAllocator(num_pages)
        self.ring, self.page_size = int(ring), int(page_size)
        self.tables = np.zeros((num_slots, self.ring), np.int32)
        self.dirty = False
        self.reused = 0                         # pages reused in place
        self._pages: List[List[int]] = [[] for _ in range(num_slots)]
        self._mapped = [0] * num_slots          # logical pages reached

    def pages_of(self, slot: int) -> List[int]:
        return list(self._pages[slot])

    def prompt_need(self, t0: int) -> int:
        """Pages a prompt of ``t0`` rows holds at admission: those of its
        last ``ring`` logical pages."""
        return min(pages_for(t0, self.page_size), self.ring)

    def short(self, slot: int, rows: int) -> int:
        """Pages that mapping ``rows`` rows of ``slot`` would allocate."""
        want = min(pages_for(rows, self.page_size), self.ring)
        return max(want - len(self._pages[slot]), 0)

    def admit(self, slot: int, t0: int, grants: List[int]):
        """Map a prompt of ``t0`` rows onto ``grants`` (``prompt_need(t0)``
        pages). -> for each LOGICAL page of the prompt its physical page,
        the trash page for those already behind the ring."""
        import numpy as np
        n = pages_for(t0, self.page_size)
        self.tables[slot, :] = TRASH_PAGE
        out = np.zeros((n,), np.int32)
        for g, j in zip(grants, range(n - len(grants), n)):
            self.tables[slot, j % self.ring] = g
            out[j] = g
        self._pages[slot] = list(grants)
        self._mapped[slot] = n
        self.dirty = True
        return out

    def grow(self, slot: int, rows: int) -> None:
        """Map every logical page of ``slot`` up to ``rows`` rows: a new
        page while the ring has a free column, else the column's own page
        again. Raises ``PagePoolExhausted`` before changing anything."""
        want = pages_for(rows, self.page_size)
        fresh = self.alloc.alloc(self.short(slot, rows))
        for j in range(self._mapped[slot], want):
            if self.tables[slot, j % self.ring] == TRASH_PAGE:
                page = fresh.pop()
                self.tables[slot, j % self.ring] = page
                self._pages[slot].append(page)
                self.dirty = True
            else:
                self.reused += 1
        self._mapped[slot] = max(self._mapped[slot], want)

    def release(self, slot: int) -> None:
        if self._pages[slot]:
            self.alloc.release(self._pages[slot])
            self._pages[slot] = []
        self.tables[slot, :] = TRASH_PAGE
        self._mapped[slot] = 0
        self.dirty = True
