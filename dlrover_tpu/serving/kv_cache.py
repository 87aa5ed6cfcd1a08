"""Paged KV cache for the serving engine.

vLLM-style paged attention, TPU-native and CPU-testable: physical KV
storage is a pool of fixed-size pages; each decode slot owns a row of a
block table mapping logical page index → physical page. Admission grabs
pages from a free list, eviction returns them — no compaction, no
per-request contiguous buffers, so slot lifetimes can interleave freely.

Two storage modes share one geometry:

- ``bf16`` — reference mode: pages hold the model compute dtype
  verbatim, so a gather reproduces a contiguous ``decoder.init_kv_cache``
  buffer bitwise (the parity baseline).
- ``int8`` — pages hold int8 payloads + one f32 scale per (token, kv
  head) using the same EQuARX-style max/127 block encode as the
  gradient wire (``ops/quant.py`` ``kv_encode_rows``), dequantized
  per-page INSIDE the jitted decode step. A token row of
  ``kv_heads*head_dim`` bf16 elements (2 bytes each) becomes ``row``
  int8 bytes + ``kv_heads`` f32 scales — a 2d/(d+4) resident-bytes
  reduction at head_dim d (1.88× at 64, 1.94× at 128).

Physical page 0 is the TRASH page: never allocated, the write target
for masked-out lanes (inactive slots, prefill-chunk padding). Gathers
clamp unassigned block-table entries (-1) onto it; whatever lands there
is garbage by construction and every reader masks it by slot position.

Host side (``PageAllocator``) is plain numpy + a free list — the engine
ships ``block_tables()`` into jit each step. Device side (``gather`` /
``write_rows``) is pure jnp so it fuses into the decode step. Live
page migration between replicas (``serving/migration.py``) holds its
survivor-side footprint through the allocator's named reservations
(``reserve_for_migration`` / ``commit_migration`` / ``abort_migration``)
so an in-flight transfer can never lose its landing pages to admission.

Committed pages are shareable: every physical page carries a refcount,
``admit_shared`` maps a prefix of another slot's pages into a new slot's
table row (rc+1, zero prefill compute for those pages), ``cow_page``
gives a slot a private copy of a shared page before it may write into
it, and ``evict`` decrements — a page returns to the free list only at
rc==0. The radix index that decides WHICH pages a new prompt can share
lives in ``serving/prefix.py``; this module only enforces the refcount
discipline.
"""

from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.ops import quant

TRASH_PAGE = 0


class PageGeometry(NamedTuple):
    """Static shape/layout contract between allocator, pools and jit."""

    n_layers: int
    kv_heads: int
    head_dim: int
    page_size: int           # tokens per page
    n_pages: int             # physical pages incl. the trash page
    max_pages_per_slot: int  # block-table width
    mode: str                # "bf16" | "int8"
    dtype: str               # model compute dtype (gather output / bf16 pools)
    kv_block: int            # int8 scale-block width (elements)

    @property
    def row_elems(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def n_blocks(self) -> int:
        return self.row_elems // self.kv_block

    @property
    def max_len(self) -> int:
        """Longest sequence one slot can hold (gather width S_max)."""
        return self.max_pages_per_slot * self.page_size


def make_geometry(
    cfg,
    *,
    n_slots: int,
    max_len: int,
    page_size: int = 16,
    mode: str = "int8",
    slack_pages: int = 0,
) -> PageGeometry:
    """Geometry sized so ``n_slots`` concurrent sequences of ``max_len``
    tokens always fit, plus ``slack_pages`` headroom and the trash page."""
    if mode not in ("bf16", "int8"):
        raise ValueError(f"mode must be bf16|int8, got {mode}")
    max_pages = -(-max_len // page_size)
    return PageGeometry(
        n_layers=cfg.n_layer,
        kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim,
        page_size=page_size,
        n_pages=1 + n_slots * max_pages + slack_pages,
        max_pages_per_slot=max_pages,
        mode=mode,
        dtype=str(cfg.dtype),
        # one scale per (token, kv head): the paged kernel dequantizes a
        # [page, heads, head_dim] tile against [page, heads] scales with
        # no cross-lane reshape, at any head count (25 x 64 included)
        kv_block=cfg.head_dim,
    )


def init_pools(geom: PageGeometry) -> Dict[str, jax.Array]:
    """Allocate the physical page pools (layer-leading, so the decoder's
    layer scan can carry gathered views as xs)."""
    g = geom
    if g.mode == "bf16":
        shape = (g.n_layers, g.n_pages, g.page_size, g.kv_heads, g.head_dim)
        dt = jnp.dtype(g.dtype)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    qshape = (g.n_layers, g.n_pages, g.page_size, g.n_blocks, g.kv_block)
    sshape = (g.n_layers, g.n_pages, g.page_size, g.n_blocks)
    return {
        "k_q": jnp.zeros(qshape, jnp.int8),
        "k_scale": jnp.zeros(sshape, jnp.float32),
        "v_q": jnp.zeros(qshape, jnp.int8),
        "v_scale": jnp.zeros(sshape, jnp.float32),
    }


def resident_bytes(geom: PageGeometry) -> int:
    """Resident KV pool bytes at this geometry."""
    g = geom
    rows = g.n_layers * g.n_pages * g.page_size
    if g.mode == "bf16":
        return 2 * rows * g.row_elems * jnp.dtype(g.dtype).itemsize
    return 2 * rows * (g.row_elems + 4 * g.n_blocks)


def stored_row_bytes(geom: PageGeometry) -> int:
    """Stored bytes of one token's K+V row (payload + int8 scales)."""
    g = geom
    if g.mode == "bf16":
        return 2 * g.row_elems * jnp.dtype(g.dtype).itemsize
    return 2 * (g.row_elems + 4 * g.n_blocks)


def decode_traffic_bytes(
    geom: PageGeometry, pages_held: int, n_slots: int, paged: bool
) -> int:
    """KV HBM bytes one decode step touches under each kernel, from
    the geometry alone (a model of the traffic, not a measurement).

    - ``paged``: every layer reads only the ``pages_held`` pages the
      whole batch holds and writes one row per slot::

          L · (pages_held · page_size + B) · stored_row_bytes

    - gather: every layer reads the FULL ``B · max_pages`` table width
      from the pools, materializes the dequantized compute-dtype copy
      (one write of ``B · S_max`` dense rows), re-reads it in
      attention, and scatters the new row back::

          L · B · S_max · (stored_row_bytes + 2 · dense_row_bytes)
          + L · B · stored_row_bytes

    Model, not measurement: it counts page/row payload traffic and
    ignores Q/O activations (identical under both kernels) — the point
    is the asymptotic split, O(pages held) vs O(table width).
    """
    g = geom
    rb = stored_row_bytes(g)
    dense = 2 * g.row_elems * jnp.dtype(g.dtype).itemsize
    if paged:
        return g.n_layers * (pages_held * g.page_size + n_slots) * rb
    smax = g.max_len
    return g.n_layers * n_slots * (smax * (rb + 2 * dense) + rb)


def gather(
    pools: Dict,
    block_tables: jax.Array,
    geom: PageGeometry,
    *,
    max_pages: int = None,
) -> Dict:
    """Materialize per-slot contiguous caches from the page pools.

    ``block_tables`` [B, max_pages] int32 (-1 = unassigned → trash page)
    → ``{"k","v"}`` [L, B, W·page_size, Hkv, D] in the model compute
    dtype, the exact layout ``decoder.decode_step`` scans. Unassigned/
    garbage positions carry finite trash values; callers mask by slot
    position.

    ``max_pages`` (static under jit) slices the gather to the first
    ``max_pages`` table entries — the host knows how many pages any
    slot actually holds, and pages are assigned in logical order, so
    the dropped tail is all ``-1``-clamped trash. Every reader masks
    by position, and masked slots contribute exact zeros through the
    f32 softmax, so a narrower gather is bitwise-invisible — it just
    stops touching (and dequantizing, in int8 mode) the whole table
    width.
    """
    g = geom
    tables = (
        block_tables if max_pages is None else block_tables[:, :max_pages]
    )
    t = jnp.maximum(tables, 0)
    b = block_tables.shape[0]
    width = t.shape[1] * g.page_size

    def _shape(x):
        return x.reshape(g.n_layers, b, width, g.kv_heads, g.head_dim)

    if g.mode == "bf16":
        return {"k": _shape(pools["k"][:, t]), "v": _shape(pools["v"][:, t])}
    dt = jnp.dtype(g.dtype)
    k = quant.kv_decode_rows(pools["k_q"][:, t], pools["k_scale"][:, t], dt)
    v = quant.kv_decode_rows(pools["v_q"][:, t], pools["v_scale"][:, t], dt)
    return {"k": _shape(k), "v": _shape(v)}


def write_rows(
    pools: Dict,
    block_tables: jax.Array,  # [B, max_pages] int32
    positions: jax.Array,     # [B, C] int32 absolute token positions
    valid: jax.Array,         # [B, C] bool — invalid lanes → trash page
    k_rows: jax.Array,        # [L, B, C, Hkv, D]
    v_rows: jax.Array,        # [L, B, C, Hkv, D]
    geom: PageGeometry,
) -> Dict:
    """Scatter token K/V rows into their slots' pages (jit-side).

    Distinct live (slot, position) pairs that WRITE always map to
    distinct (page, offset) cells: the allocator hands a fresh page to
    exactly one slot, and a shared page (rc > 1, prefix sharing) is
    read-only by contract — the engine COW-duplicates it before any
    sharer may write past the committed prefix. Only trash-page lanes
    may collide, and those are garbage by construction."""
    g = geom
    page_idx = positions // g.page_size
    offs = positions % g.page_size
    phys = jnp.take_along_axis(block_tables, page_idx, axis=1)
    phys = jnp.where(valid, jnp.maximum(phys, 0), TRASH_PAGE)
    offs = jnp.where(valid, offs, 0)
    if g.mode == "bf16":
        dt = pools["k"].dtype
        return {
            "k": pools["k"].at[:, phys, offs].set(k_rows.astype(dt)),
            "v": pools["v"].at[:, phys, offs].set(v_rows.astype(dt)),
        }
    lead = k_rows.shape[:3]
    kq, ks = quant.kv_encode_rows(k_rows.reshape(*lead, g.row_elems),
                                  g.kv_block)
    vq, vs = quant.kv_encode_rows(v_rows.reshape(*lead, g.row_elems),
                                  g.kv_block)
    return {
        "k_q": pools["k_q"].at[:, phys, offs].set(kq),
        "k_scale": pools["k_scale"].at[:, phys, offs].set(ks),
        "v_q": pools["v_q"].at[:, phys, offs].set(vq),
        "v_scale": pools["v_scale"].at[:, phys, offs].set(vs),
    }


class PageAllocator:
    """Host-side block-table allocator over the physical page pool.

    Invariants (pinned by the property test in
    tests/test_serving_kv_cache.py):

    - every physical page's refcount equals the number of (slot, logical)
      table cells mapping it — 1 for a private page, >1 when prefix
      sharing maps one committed page into several slots;
    - page 0 (trash) is never handed out;
    - ``evict`` decrements each held page's refcount and frees only the
      pages that reach rc==0 (sharers keep the rest alive);
    - free + assigned-unique (rc ≥ 1) + reserved is a partition of pages
      1..n_pages-1.

    Reservations are the migration footprint hold: pages moved from the
    free list into a named bucket, invisible to ``can_admit``/``ensure``
    until ``commit_migration`` assigns them to a slot or
    ``abort_migration`` returns them. Mutations are not locked — callers
    serialize through the engine thread (or ``GenerationServer.paused()``).

    ``on_free`` (optional) fires with the list of physical pages whose
    refcount just hit zero — the prefix index hangs its invalidation off
    this so a recycled page can never be offered as a prefix hit.
    """

    def __init__(self, geom: PageGeometry, n_slots: int):
        self.geom = geom
        self.n_slots = n_slots
        # pop() yields ascending physical pages — deterministic layouts
        self._free = list(range(geom.n_pages - 1, TRASH_PAGE, -1))
        self._reserved: Dict[str, List[int]] = {}
        self._tables = np.full(
            (n_slots, geom.max_pages_per_slot), -1, np.int32
        )
        self._n_pages = np.zeros(n_slots, np.int32)
        # per-physical-page refcount: number of (slot, logical) cells
        # mapping the page. Free and reserved pages sit at 0.
        self._rc = np.zeros(geom.n_pages, np.int32)
        # set by every table mutation; the engine consumes it to re-ship
        # the device copy only when something actually changed
        self._dirty = True
        # cached host-side snapshot for block_tables(); invalidated by
        # the same mutations that set _dirty (but cleared independently:
        # consume_dirty() must not force the next block_tables() to copy)
        self._snap: Optional[np.ndarray] = None
        self.on_free: Optional[Callable[[List[int]], None]] = None

    # ---- queries ---------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_needed(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 0) // self.geom.page_size)

    def can_admit(self, n_tokens: int, n_shared: int = 0) -> bool:
        """True when a slot covering ``n_tokens`` fits. ``n_shared``
        discounts prefix pages that would be MAPPED rather than drawn
        from the free list (a prefix hit's read-only shared pages —
        COW'd tail pages are fresh allocations and get no discount)."""
        need = self.pages_needed(n_tokens)
        return (
            need <= self.geom.max_pages_per_slot
            and need - min(int(n_shared), need) <= len(self._free)
        )

    def slot_pages(self, slot: int) -> int:
        return int(self._n_pages[slot])

    def refcount(self, page: int) -> int:
        return int(self._rc[page])

    @property
    def unique_assigned_pages(self) -> int:
        """Distinct physical pages held by any slot — the denominator of
        the dedup ratio (Σ slot cells / unique pages)."""
        return int(np.count_nonzero(self._rc))

    @property
    def reserved_pages(self) -> int:
        return sum(len(p) for p in self._reserved.values())

    def reservation(self, tag: str) -> Tuple[int, ...]:
        """The physical pages held under ``tag`` (empty if none)."""
        return tuple(self._reserved.get(tag, ()))

    def block_tables(self) -> np.ndarray:
        """A host-side snapshot of the [n_slots, max_pages] table.

        The snapshot is cached between mutations: the common steady
        state (no admit/grow/evict this step) returns the SAME array
        without re-copying. Mutations write ``self._tables`` and drop
        the cache, so a previously returned snapshot never aliases a
        buffer ``evict``/``ensure`` mutates mid-step — callers may hand
        it to jit or keep it across steps."""
        if self._snap is None:
            self._snap = self._tables.copy()
        return self._snap

    def consume_dirty(self) -> bool:
        """True exactly once after any table mutation since the last
        call (admit/grow/evict). Lets the engine skip the per-step
        host-to-device block-table transfer on the (common) steps where
        no slot changed shape."""
        d = self._dirty
        self._dirty = False
        return d

    # ---- transitions -----------------------------------------------------

    def admit(self, slot: int, n_tokens: int) -> bool:
        """Assign pages covering ``n_tokens`` to an EMPTY slot."""
        if self._n_pages[slot]:
            raise ValueError(f"slot {slot} already holds pages")
        return self.ensure(slot, n_tokens)

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot`` to cover ``n_tokens`` total; False (state
        unchanged) when the free list cannot cover the growth."""
        need = self.pages_needed(n_tokens)
        if need > self.geom.max_pages_per_slot:
            return False
        have = int(self._n_pages[slot])
        grow = need - have
        if grow <= 0:
            return True
        if grow > len(self._free):
            return False
        for i in range(have, need):
            p = self._free.pop()
            self._tables[slot, i] = p
            self._rc[p] = 1
        self._n_pages[slot] = need
        self._dirty = True
        self._snap = None
        return True

    def admit_shared(
        self, slot: int, n_tokens: int, prefix_pages: Sequence[int]
    ) -> bool:
        """Admit an EMPTY slot covering ``n_tokens``, mapping logical
        pages 0..len(prefix_pages)-1 onto EXISTING physical pages
        (rc+1 each — a prefix hit) and drawing the remainder fresh.
        False (state unchanged) when the free list cannot cover the
        unshared suffix. Shared pages are read-only for this slot until
        ``cow_page`` gives it a private copy."""
        if self._n_pages[slot]:
            raise ValueError(f"slot {slot} already holds pages")
        need = self.pages_needed(n_tokens)
        shared = list(prefix_pages)
        if len(shared) > need:
            raise ValueError(
                f"prefix ({len(shared)} pages) exceeds footprint ({need})"
            )
        if need > self.geom.max_pages_per_slot:
            return False
        if need - len(shared) > len(self._free):
            return False
        for p in shared:  # validate BEFORE mutating — no partial maps
            if not (TRASH_PAGE < p < self.geom.n_pages) or self._rc[p] < 1:
                raise ValueError(f"prefix page {p} is not live")
        for i, p in enumerate(shared):
            self._tables[slot, i] = p
            self._rc[p] += 1
        for i in range(len(shared), need):
            p = self._free.pop()
            self._tables[slot, i] = p
            self._rc[p] = 1
        self._n_pages[slot] = need
        if need:
            self._dirty = True
            self._snap = None
        return True

    def cow_page(self, slot: int, logical: int) -> Optional[Tuple[int, int]]:
        """Give ``slot`` a private copy of its ``logical`` page before it
        writes into it. No-op (returns None) when the page is already
        private (rc==1). Otherwise pops a fresh page, remaps the cell,
        and returns ``(src, dst)`` physical pages — the caller copies the
        pool payload device-side. Raises when the free list is empty:
        the admission footprint must already have accounted for the COW
        page (``can_admit`` gives shared discounts only to read-only
        prefix pages)."""
        if not 0 <= logical < int(self._n_pages[slot]):
            raise ValueError(f"slot {slot} has no logical page {logical}")
        src = int(self._tables[slot, logical])
        if self._rc[src] == 1:
            return None
        if not self._free:
            raise RuntimeError("cow_page: free list empty (footprint bug)")
        dst = self._free.pop()
        self._tables[slot, logical] = dst
        self._rc[src] -= 1
        self._rc[dst] = 1
        self._dirty = True
        self._snap = None
        return src, dst

    def evict(self, slot: int) -> int:
        """Release every page the slot holds (rc−1 each; pages reaching
        rc==0 return to the free list); returns the CELL count released
        — the slot's logical footprint, not the pages actually freed."""
        n = int(self._n_pages[slot])
        freed: List[int] = []
        for i in range(n):
            p = int(self._tables[slot, i])
            self._rc[p] -= 1
            if self._rc[p] == 0:
                self._free.append(p)
                freed.append(p)
        self._tables[slot, :] = -1
        self._n_pages[slot] = 0
        if n:
            self._dirty = True
            self._snap = None
        if freed and self.on_free is not None:
            self.on_free(freed)
        return n

    # ---- migration reservations ------------------------------------------

    def reserve_for_migration(self, tag: str, n_tokens: int) -> bool:
        """Hold the full page footprint for an incoming migrated request
        under ``tag``. False (state unchanged) when the free list cannot
        cover it — the migrator sheds/backs off and retries."""
        if tag in self._reserved:
            raise ValueError(f"migration tag {tag!r} already reserved")
        need = self.pages_needed(n_tokens)
        if need > self.geom.max_pages_per_slot or need > len(self._free):
            return False
        self._reserved[tag] = [self._free.pop() for _ in range(need)]
        return True

    def commit_migration(self, tag: str, slot: int) -> List[int]:
        """Assign the reservation's pages to an EMPTY slot's table row,
        in reservation order (logical page i → reserved page i). Returns
        the physical pages so the importer can scatter payloads."""
        if tag not in self._reserved:
            raise KeyError(f"no migration reservation {tag!r}")
        if self._n_pages[slot]:
            raise ValueError(f"slot {slot} already holds pages")
        pages = self._reserved.pop(tag)
        for i, p in enumerate(pages):
            self._tables[slot, i] = p
            self._rc[p] = 1
        self._n_pages[slot] = len(pages)
        if pages:
            self._dirty = True
            self._snap = None
        return list(pages)

    def abort_migration(self, tag: str) -> int:
        """Return a reservation's pages to the free list (torn transfer,
        fallback to re-prefill). Missing tag is a no-op — abort must be
        safe to call from any phase's unwind."""
        pages = self._reserved.pop(tag, [])
        self._free.extend(pages)
        return len(pages)
