"""Paged KV cache invariants (serving/kv_cache.py).

Property test over random admit/grow/evict/share/cow/reserve/commit/
abort traces: refcount conservation — every physical page's rc equals
the number of (slot, logical) table cells mapping it — the trash page
is never handed out, eviction decrements and frees only rc==0 pages,
and free + assigned-unique + migration-reserved stays a partition of
pages 1..n_pages-1 at every step. Device-side: bf16 pages round-trip
bitwise, int8 pages round-trip within the per-block scale bound, and
the int8 geometry's resident bytes beat bf16 by ≥1.7×.
"""

from collections import Counter

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models.config import get_config  # noqa: E402
from dlrover_tpu.ops import quant  # noqa: E402
from dlrover_tpu.serving import kv_cache as kvc  # noqa: E402


def _cfg(**kw):
    base = dict(
        n_layer=2, d_model=32, d_ff=64, n_head=4, vocab_size=32, max_seq=64
    )
    base.update(kw)
    return get_config("tiny", **base)


def _check_partition(alloc, geom):
    """Refcount conservation + partition: every page's rc equals the
    number of (slot, logical) cells mapping it, and free +
    assigned-unique (rc ≥ 1) + reserved partitions pages 1..n_pages-1,
    trash excluded."""
    cells = Counter(
        int(p) for row in alloc._tables for p in row if p >= 0
    )
    for page in range(geom.n_pages):
        assert alloc.refcount(page) == cells.get(page, 0), page
    reserved = [int(p) for ps in alloc._reserved.values() for p in ps]
    assigned = set(cells)
    assert kvc.TRASH_PAGE not in assigned, "trash page handed out"
    assert kvc.TRASH_PAGE not in reserved, "trash page reserved"
    assert len(reserved) == len(set(reserved)), "double-reserved page"
    assert not assigned & set(reserved), "reserved page is mapped"
    free = set(alloc._free)
    assert len(alloc._free) == len(free), "duplicate free-list entry"
    universe = set(range(1, geom.n_pages))
    assert assigned | set(reserved) | free == universe
    assert not free & assigned and not free & set(reserved)
    assert alloc.reserved_pages == len(reserved)
    assert alloc.unique_assigned_pages == len(assigned)


def test_allocator_random_trace_property():
    geom = kvc.make_geometry(
        _cfg(), n_slots=4, max_len=40, page_size=4, mode="int8"
    )
    alloc = kvc.PageAllocator(geom, 4)
    # on_free discipline: fires only for pages whose rc hit zero, and
    # those pages must be back on the free list when it fires
    def _on_free(pages):
        for p in pages:
            assert alloc.refcount(p) == 0
            assert p in alloc._free
    alloc.on_free = _on_free
    rng = np.random.default_rng(0)
    held = [0, 0, 0, 0]  # tokens covered per slot
    reservations = {}    # tag -> n_tokens reserved for migration
    tag_seq = 0
    for _ in range(400):
        slot = int(rng.integers(0, 4))
        op = rng.choice(
            ["admit", "grow", "evict", "share", "cow",
             "reserve", "commit", "abort"]
        )
        if op == "admit" and held[slot] == 0:
            n = int(rng.integers(1, geom.max_len + 5))
            before = alloc.free_pages
            ok = alloc.admit(slot, n)
            assert ok == (
                alloc.pages_needed(n) <= geom.max_pages_per_slot
                and alloc.pages_needed(n) <= before
            )
            if ok:
                held[slot] = n
        elif op == "grow" and held[slot] > 0:
            n = held[slot] + int(rng.integers(0, 8))
            before_free = alloc.free_pages
            before_pages = alloc.slot_pages(slot)
            ok = alloc.ensure(slot, n)
            if ok:
                held[slot] = max(held[slot], n)
            else:
                # failed growth must not leak or steal pages
                assert alloc.free_pages == before_free
                assert alloc.slot_pages(slot) == before_pages
        elif op == "evict":
            # with sharing live this is the RELEASE op: rc−1 per cell,
            # only rc==0 pages return to the free list — a sharer's
            # eviction must never free a sharee's pages
            n_pages = alloc.slot_pages(slot)
            shared_out = sum(
                1
                for p in alloc._tables[slot, :n_pages]
                if alloc.refcount(int(p)) > 1
            )
            before_free = alloc.free_pages
            freed = alloc.evict(slot)
            assert freed == n_pages  # cell count, sharing-invisible
            assert alloc.free_pages == before_free + n_pages - shared_out
            held[slot] = 0
            assert alloc.slot_pages(slot) == 0
        elif op == "share" and held[slot] == 0:
            donors = [
                d for d in range(4) if d != slot and alloc.slot_pages(d)
            ]
            if not donors:
                continue
            donor = donors[int(rng.integers(0, len(donors)))]
            m = int(rng.integers(1, alloc.slot_pages(donor) + 1))
            prefix = [int(p) for p in alloc.block_tables()[donor, :m]]
            n = int(rng.integers(m * geom.page_size, geom.max_len + 5))
            before = alloc.free_pages
            rc_before = [alloc.refcount(p) for p in prefix]
            need = alloc.pages_needed(n)
            ok = alloc.admit_shared(slot, n, prefix)
            assert ok == (
                need <= geom.max_pages_per_slot
                and need - m <= before
            )
            if ok:
                held[slot] = n
                assert alloc.free_pages == before - (need - m)
                for p, rc in zip(prefix, rc_before):
                    assert alloc.refcount(p) == rc + 1
            else:
                assert alloc.free_pages == before
                for p, rc in zip(prefix, rc_before):
                    assert alloc.refcount(p) == rc
        elif op == "cow" and held[slot] > 0:
            logical = int(rng.integers(0, alloc.slot_pages(slot)))
            src = int(alloc.block_tables()[slot, logical])
            if alloc.refcount(src) == 1:
                assert alloc.cow_page(slot, logical) is None
            elif alloc.free_pages == 0:
                with pytest.raises(RuntimeError):
                    alloc.cow_page(slot, logical)
            else:
                rc_src = alloc.refcount(src)
                got_src, dst = alloc.cow_page(slot, logical)
                assert got_src == src
                assert alloc.refcount(src) == rc_src - 1
                assert alloc.refcount(dst) == 1
                assert int(alloc.block_tables()[slot, logical]) == dst
        elif op == "reserve":
            tag = f"mig-{tag_seq}"
            tag_seq += 1
            n = int(rng.integers(1, geom.max_len + 5))
            before = alloc.free_pages
            ok = alloc.reserve_for_migration(tag, n)
            assert ok == (
                alloc.pages_needed(n) <= geom.max_pages_per_slot
                and alloc.pages_needed(n) <= before
            )
            if ok:
                reservations[tag] = n
                assert len(alloc.reservation(tag)) == alloc.pages_needed(n)
            else:
                # failed reservation must not leak pages or leave a tag
                assert alloc.free_pages == before
                assert alloc.reservation(tag) == ()
        elif op == "commit" and reservations and held[slot] == 0:
            tag = next(iter(reservations))
            n = reservations.pop(tag)
            pages = alloc.commit_migration(tag, slot)
            assert len(pages) == alloc.pages_needed(n)
            assert alloc.slot_pages(slot) == len(pages)
            held[slot] = n
        elif op == "abort" and reservations:
            tag = next(iter(reservations))
            n = reservations.pop(tag)
            before = alloc.free_pages
            freed = alloc.abort_migration(tag)
            assert freed == alloc.pages_needed(n)
            assert alloc.free_pages == before + freed
        _check_partition(alloc, geom)
    # drain: after aborting/evicting everything the free list is whole
    for tag in list(reservations):
        alloc.abort_migration(tag)
    for s in range(4):
        alloc.evict(s)
    assert alloc.free_pages == geom.n_pages - 1
    assert alloc.reserved_pages == 0
    _check_partition(alloc, geom)


def test_reserve_commit_abort_edges():
    geom = kvc.make_geometry(
        _cfg(), n_slots=2, max_len=16, page_size=4, mode="bf16"
    )
    alloc = kvc.PageAllocator(geom, 2)
    assert alloc.reserve_for_migration("a", 9)
    with pytest.raises(ValueError):        # duplicate tag
        alloc.reserve_for_migration("a", 1)
    with pytest.raises(KeyError):          # unknown tag
        alloc.commit_migration("ghost", 0)
    assert alloc.admit(0, 5)
    with pytest.raises(ValueError):        # occupied slot
        alloc.commit_migration("a", 0)
    pages = alloc.commit_migration("a", 1)
    assert len(pages) == alloc.pages_needed(9) == alloc.slot_pages(1)
    assert alloc.abort_migration("ghost") == 0   # abort is idempotent
    _check_partition(alloc, geom)


def test_admit_rejects_nonempty_slot():
    geom = kvc.make_geometry(
        _cfg(), n_slots=2, max_len=16, page_size=4, mode="bf16"
    )
    alloc = kvc.PageAllocator(geom, 2)
    assert alloc.admit(0, 5)
    with pytest.raises(ValueError):
        alloc.admit(0, 3)


def test_bf16_pages_roundtrip_bitwise():
    cfg = _cfg()
    geom = kvc.make_geometry(
        cfg, n_slots=2, max_len=16, page_size=4, mode="bf16"
    )
    alloc = kvc.PageAllocator(geom, 2)
    assert alloc.admit(0, 9) and alloc.admit(1, 6)
    pools = kvc.init_pools(geom)
    tables = jnp.asarray(alloc.block_tables())
    L, B, C = cfg.n_layer, 2, 3
    shape = (L, B, C, cfg.kv_heads, cfg.head_dim)
    k = jax.random.normal(jax.random.key(1), shape).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), shape).astype(jnp.bfloat16)
    positions = jnp.array([[0, 4, 8], [1, 3, 5]], jnp.int32)
    valid = jnp.ones((B, C), bool)
    pools = kvc.write_rows(pools, tables, positions, valid, k, v, geom)
    got = kvc.gather(pools, tables, geom)
    for b in range(B):
        for ci in range(C):
            pos = int(positions[b, ci])
            np.testing.assert_array_equal(
                np.asarray(got["k"][:, b, pos]), np.asarray(k[:, b, ci])
            )
            np.testing.assert_array_equal(
                np.asarray(got["v"][:, b, pos]), np.asarray(v[:, b, ci])
            )


def test_int8_pages_roundtrip_within_scale_bound():
    cfg = _cfg()
    geom = kvc.make_geometry(
        cfg, n_slots=2, max_len=16, page_size=4, mode="int8"
    )
    alloc = kvc.PageAllocator(geom, 2)
    assert alloc.admit(0, 8) and alloc.admit(1, 8)
    pools = kvc.init_pools(geom)
    tables = jnp.asarray(alloc.block_tables())
    L, B, C = cfg.n_layer, 2, 4
    shape = (L, B, C, cfg.kv_heads, cfg.head_dim)
    k = jax.random.normal(jax.random.key(3), shape).astype(jnp.float32)
    v = jax.random.normal(jax.random.key(4), shape).astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    valid = jnp.ones((B, C), bool)
    pools = kvc.write_rows(pools, tables, positions, valid, k, v, geom)
    got = kvc.gather(pools, tables, geom)
    row = geom.row_elems
    for b in range(B):
        for ci in range(C):
            ref = np.asarray(k[:, b, ci], np.float32).reshape(L, row)
            dec = np.asarray(
                got["k"][:, b, ci], np.float32
            ).reshape(L, row)
            # per-block bound: quantization error ≤ scale/2 + bf16
            # rounding of the dequantized value
            blocks = ref.reshape(L, geom.n_blocks, geom.kv_block)
            scale = np.abs(blocks).max(-1, keepdims=True) / 127.0
            bound = np.broadcast_to(
                scale * 0.51 + 2e-2, blocks.shape
            ).reshape(L, row)
            assert (np.abs(ref - dec) <= bound).all()


def test_invalid_lanes_hit_trash_page_only():
    cfg = _cfg()
    geom = kvc.make_geometry(
        cfg, n_slots=2, max_len=16, page_size=4, mode="bf16"
    )
    alloc = kvc.PageAllocator(geom, 2)
    assert alloc.admit(0, 8)
    pools = kvc.init_pools(geom)
    tables = jnp.asarray(alloc.block_tables())
    L, B, C = cfg.n_layer, 2, 2
    shape = (L, B, C, cfg.kv_heads, cfg.head_dim)
    k = jnp.ones(shape, jnp.bfloat16)
    v = jnp.ones(shape, jnp.bfloat16)
    positions = jnp.zeros((B, C), jnp.int32)
    # slot 1 has NO pages (table row all -1) and is fully invalid
    valid = jnp.array([[True, True], [False, False]])
    pools = kvc.write_rows(pools, tables, positions, valid, k, v, geom)
    # every allocated page except slot 0's first stays zero
    pool_k = np.asarray(pools["k"], np.float32)
    slot0_page = int(alloc.block_tables()[0, 0])
    for page in range(1, geom.n_pages):
        if page == slot0_page:
            continue
        assert (pool_k[:, page] == 0).all(), page


def test_consume_dirty_true_once_per_mutation():
    geom = kvc.make_geometry(
        _cfg(), n_slots=2, max_len=16, page_size=4, mode="bf16"
    )
    alloc = kvc.PageAllocator(geom, 2)
    assert alloc.consume_dirty()       # fresh tables must ship once
    assert not alloc.consume_dirty()   # ...and only once
    assert alloc.admit(0, 5)
    assert alloc.consume_dirty()
    assert not alloc.consume_dirty()
    assert alloc.ensure(0, 6)          # covered already: no new page
    assert not alloc.consume_dirty()
    assert alloc.ensure(0, 9)          # grows by a page
    assert alloc.consume_dirty()
    assert alloc.evict(1) == 0         # empty slot: nothing changed
    assert not alloc.consume_dirty()
    assert alloc.evict(0) == 3
    assert alloc.consume_dirty()


def test_block_tables_snapshot_cached_until_mutation():
    """The common no-mutation step must not pay a full-array copy:
    ``block_tables()`` returns the SAME snapshot until the allocator
    mutates, and an old snapshot never aliases the live buffer."""
    geom = kvc.make_geometry(
        _cfg(), n_slots=2, max_len=16, page_size=4, mode="bf16"
    )
    alloc = kvc.PageAllocator(geom, 2)
    t1 = alloc.block_tables()
    assert alloc.block_tables() is t1        # cached, no re-copy
    assert alloc.admit(0, 5)
    t2 = alloc.block_tables()
    assert t2 is not t1                      # mutation invalidates
    assert (t1 == -1).all()                  # old snapshot frozen
    assert alloc.block_tables() is t2
    # the cache is independent of consume_dirty: the engine draining
    # the dirty flag must not force the next block_tables() to copy
    assert alloc.consume_dirty()
    assert alloc.block_tables() is t2
    assert alloc.evict(0) == 2
    t3 = alloc.block_tables()
    assert t3 is not t2 and int(t2[0, 0]) >= 0
    # cow + shared admission invalidate too (table cells change)
    assert alloc.admit(0, 5)
    row = [int(p) for p in alloc.block_tables()[0, :1]]
    t4 = alloc.block_tables()
    assert alloc.admit_shared(1, 4, row)
    assert alloc.block_tables() is not t4
    t5 = alloc.block_tables()
    assert alloc.cow_page(1, 0) is not None
    assert alloc.block_tables() is not t5


def test_share_and_cow_edges():
    geom = kvc.make_geometry(
        _cfg(), n_slots=3, max_len=16, page_size=4, mode="bf16"
    )
    alloc = kvc.PageAllocator(geom, 3)
    assert alloc.admit(0, 16)
    row = [int(p) for p in alloc.block_tables()[0]]
    with pytest.raises(ValueError):   # occupied slot
        alloc.admit_shared(0, 8, row[:1])
    with pytest.raises(ValueError):   # prefix longer than footprint
        alloc.admit_shared(1, 4, row[:3])
    with pytest.raises(ValueError):   # trash page is never shareable
        alloc.admit_shared(1, 8, [kvc.TRASH_PAGE])
    free = alloc.free_pages
    with pytest.raises(ValueError):   # dead page is not shareable
        alloc.admit_shared(1, 8, [alloc._free[-1]])
    assert alloc.admit_shared(1, 16, row)   # full-row share: no fresh
    assert alloc.free_pages == free
    assert all(alloc.refcount(p) == 2 for p in row)
    with pytest.raises(ValueError):   # no such logical page
        alloc.cow_page(2, 0)
    _check_partition(alloc, geom)


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_partial_gather_bitwise_equals_sliced_full(mode):
    """gather(max_pages=W) must equal the first W·page_size positions
    of the full gather BITWISE — held pages are a table prefix, so the
    narrower gather only drops -1-clamped trash."""
    cfg = _cfg()
    geom = kvc.make_geometry(
        cfg, n_slots=2, max_len=32, page_size=4, mode=mode
    )
    alloc = kvc.PageAllocator(geom, 2)
    assert alloc.admit(0, 9) and alloc.admit(1, 14)
    pools = kvc.init_pools(geom)
    tables = jnp.asarray(alloc.block_tables())
    L, B, C = cfg.n_layer, 2, 14
    shape = (L, B, C, cfg.kv_heads, cfg.head_dim)
    k = jax.random.normal(jax.random.key(11), shape).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.key(12), shape).astype(jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    valid = jnp.asarray(
        np.arange(C)[None, :] < np.asarray([9, 14])[:, None]
    )
    pools = kvc.write_rows(pools, tables, positions, valid, k, v, geom)
    held = max(alloc.slot_pages(0), alloc.slot_pages(1))
    full = kvc.gather(pools, tables, geom)
    part = kvc.gather(pools, tables, geom, max_pages=held)
    width = held * geom.page_size
    for key in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(part[key]),
            np.asarray(full[key][:, :, :width]),
        )


def test_resident_bytes_reduction_vs_bf16():
    # one f32 scale per (token, kv head): 2d / (d + 4) at head_dim d —
    # 1.88x at GPT-2 XL's 64-wide heads, 1.94x at 128
    for d_model, n_head in ((1600, 25), (256, 4), (2048, 16)):
        cfg = _cfg(d_model=d_model, n_head=n_head)
        g8 = kvc.make_geometry(
            cfg, n_slots=2, max_len=32, page_size=8, mode="int8"
        )
        assert g8.kv_block == cfg.head_dim
        g16 = g8._replace(mode="bf16")
        ratio = kvc.resident_bytes(g16) / kvc.resident_bytes(g8)
        assert ratio >= 1.85, (d_model, ratio)


def test_decode_traffic_model_asymptotics():
    """The bench's HBM model: paged traffic scales with pages held and
    stays below the gather cost, which is O(S_max) and independent of
    what is actually resident."""
    geom = kvc.make_geometry(
        _cfg(), n_slots=4, max_len=256, page_size=8, mode="int8"
    )
    few = kvc.decode_traffic_bytes(geom, 8, 4, paged=True)
    many = kvc.decode_traffic_bytes(geom, 64, 4, paged=True)
    gather = kvc.decode_traffic_bytes(geom, 8, 4, paged=False)
    assert 0 < few < many < gather
    # gather cost ignores pages_held entirely — full table width
    assert gather == kvc.decode_traffic_bytes(geom, 64, 4, paged=False)


def test_kv_block_size_divides_rows():
    for row in (8, 32, 96, 128, 256, 320, 384, 1024):
        blk = quant.kv_block_size(row)
        assert 1 <= blk <= 256
        assert row % blk == 0


def test_geometry_validates_mode():
    with pytest.raises(ValueError):
        kvc.make_geometry(_cfg(), n_slots=1, max_len=8, mode="fp4")
