"""Paged KV accounting: BlockAllocator invariants (unit + hypothesis
property tests) and the shared admission/extension/preemption policies
both execution backends drive (core/paging.py, DESIGN.md §3).

Invariants (generalized for refcounted prefix sharing, PR 3, and the
host spill tier, PR 5):
  * a page's refcount always equals (#live tables holding it) + (#pins)
    — no page is freed while referenced;
  * free + unique-live + spilled == total: device pages satisfy
    free + unique-live == n_pages (a spilled page's HBM genuinely
    frees) and host slots satisfy free-host + spilled == host_pages,
    across any alloc/share/extend/pin/unpin/release/spill/restore
    interleaving;
  * a SHARED page never spills (refused unless the caller's pin is the
    last reference); restore is idempotent (begin returns the same
    reserved page, a second commit is a no-op);
  * a live request's table covers exactly ceil(tokens / page_size)
    pages;
  * alloc/extend are all-or-nothing (failed calls change nothing);
  * release is idempotent per rid.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.batcher import FormedBatch
from repro.core.paging import (BlockAllocator, PagedKv, admit_blocks,
                               device_pool_pages, extend_for_decode,
                               host_tier_geometry)
from repro.core.request import Request

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # unit tests below still run without it
    HAVE_HYPOTHESIS = False


def _req(rid, plen=10, mnt=4, arrival=0.0):
    return Request(rid=rid, prompt_len=plen, max_new_tokens=mnt,
                   arrival=arrival)


# ------------------------------------------------------------ unit tests --
class TestBlockAllocator:
    def test_alloc_covers_ceil_pages(self):
        a = BlockAllocator(n_pages=10, page_size=16)
        assert len(a.alloc(0, 1)) == 1
        assert len(a.alloc(1, 16)) == 1
        assert len(a.alloc(2, 17)) == 2
        assert a.free_pages() == 6
        assert a.live_pages() == 4

    def test_exhaustion_is_all_or_nothing(self):
        a = BlockAllocator(n_pages=3, page_size=8)
        assert a.alloc(0, 16) is not None            # 2 pages
        free_before = a.free_pages()
        assert a.alloc(1, 17) is None                # needs 3, has 1
        assert a.free_pages() == free_before         # state unchanged
        assert not a.holds(1)

    def test_extend_grows_by_pages(self):
        a = BlockAllocator(n_pages=4, page_size=8)
        t0 = a.alloc(0, 8)
        assert a.extend(0, 8) == []                  # still 1 page
        new = a.extend(0, 9)                         # crosses the boundary
        assert len(new) == 1 and new[0] not in t0
        assert a.table(0) == t0 + new
        assert a.extend(0, 5) == []                  # tables never shrink

    def test_extend_exhaustion_unchanged(self):
        a = BlockAllocator(n_pages=2, page_size=8)
        a.alloc(0, 8)
        a.alloc(1, 8)
        before = a.table(0)
        assert a.extend(0, 9) is None
        assert a.table(0) == before

    def test_release_idempotent_and_recycles(self):
        a = BlockAllocator(n_pages=2, page_size=8)
        pages = a.alloc(0, 16)
        assert a.release(0) == 2
        assert a.release(0) == 0                     # idempotent
        assert sorted(a.alloc(1, 16)) == sorted(pages)

    def test_no_double_assignment(self):
        a = BlockAllocator(n_pages=8, page_size=4)
        seen = set()
        for rid in range(4):
            for p in a.alloc(rid, 8):
                assert p not in seen
                seen.add(p)

    def test_shared_alloc_refcounts(self):
        """A shared prefix page lives in BOTH tables, is counted once in
        live_pages, and is freed only when the LAST reference drops."""
        a = BlockAllocator(n_pages=4, page_size=8)
        t0 = a.alloc(0, 16)                          # 2 pages
        t1 = a.alloc(1, 17, shared=t0[:2])           # shares both + 1 new
        assert t1[:2] == t0[:2] and len(t1) == 3
        assert a.live_pages() == 3                   # unique pages
        assert a.free_pages() + a.live_pages() == 4
        assert a.refs(t0[0]) == 2 and a.shared_pages() == 2
        assert a.release(0) == 0                     # nothing freed: shared
        assert a.refs(t0[0]) == 1
        assert a.release(1) == 3                     # last refs drop
        assert a.free_pages() == 4 and a.live_pages() == 0

    def test_shared_alloc_all_or_nothing_keeps_refs(self):
        """A failed shared alloc must not leave refcount bumps behind."""
        a = BlockAllocator(n_pages=3, page_size=8)
        t0 = a.alloc(0, 16)
        a.alloc(1, 8)                                # pool now full
        before = a.refs(t0[0])
        assert a.alloc(2, 32, shared=t0) is None     # needs 2 free, has 0
        assert a.refs(t0[0]) == before
        assert not a.holds(2)

    def test_pin_unpin_survives_release(self):
        """A cache pin keeps a page alive past its writer's release
        (the prefix-cache lifetime rule)."""
        a = BlockAllocator(n_pages=2, page_size=8)
        t = a.alloc(0, 8)
        a.pin(t[0])
        assert a.release(0) == 0                     # pinned: not freed
        assert a.refs(t[0]) == 1 and a.free_pages() == 1
        assert a.unpin(t[0]) is True                 # now it frees
        assert a.free_pages() == 2

    def test_reclaimable_counts_only_sole_refs(self):
        a = BlockAllocator(n_pages=4, page_size=8)
        t0 = a.alloc(0, 16)
        a.alloc(1, 24, shared=t0[:2])                # 2 shared + 1 private
        assert a.reclaimable(0) == 0                 # both pages shared
        assert a.reclaimable(1) == 1                 # only its private page

    def test_byte_denominated_tier_accounting(self):
        """Device and host occupancy are priced in each tier's OWN
        bytes: a spilled page stops costing device bytes and starts
        costing (smaller, compressed) host-slot bytes."""
        a = BlockAllocator(n_pages=4, page_size=8, host_pages=2,
                           page_bytes=1024, host_slot_bytes=288)
        a.alloc(0, 17)                               # 3 pages
        assert a.device_bytes_in_use() == 3 * 1024
        assert a.host_bytes_in_use() == 0
        t = a.alloc(1, 8)
        a.pin(t[0])
        a.release(1)
        h = a.spill(t[0])
        assert h is not None
        assert a.device_bytes_in_use() == 3 * 1024   # page's HBM freed
        assert a.host_bytes_in_use() == 288          # compressed slot
        p = a.restore_begin(h)
        a.restore_commit(h)
        assert a.host_bytes_in_use() == 0
        assert a.device_bytes_in_use() == 4 * 1024
        a.unpin(p)


class TestTierByteDenomination:
    """Tentpole: pool sizing is byte-denominated per tier.  The token
    budgets (``kv_pool_tokens`` / ``host_pool_tokens``) are
    bf16-REFERENCE byte quantities, so a compressed tier fits more
    pages under the SAME budget — and the bf16 tier is bit-compatible
    with the old token-denominated sizing."""

    def test_bf16_pool_backcompat_exact(self):
        cfg = get_config("llama2-13b")
        assert device_pool_pages(cfg, 64 * 128, 128) == 64
        n, slot = host_tier_geometry(cfg, 64 * 128, 128, "")
        assert n == 64
        assert slot == 128 * cfg.cache_bytes_per_token()

    def test_int8_pool_nearly_doubles_pages(self):
        pages = device_pool_pages(get_config("llama2-13b"), 64 * 128, 128)
        pages8 = device_pool_pages(get_config("llama2-13b", variant="int8"),
                                   64 * 128, 128)
        assert pages8 >= int(1.8 * pages)

    def test_host_geometry_compression_ladder(self):
        cfg = get_config("llama2-13b")
        budget_tokens = 64 * 128
        slots = {}
        for dt in ("", "int8", "int4"):
            n, slot = host_tier_geometry(cfg, budget_tokens, 128, dt)
            assert slot == 128 * cfg.spill_bytes_per_token(dt)
            # never oversubscribes the byte budget
            assert n * slot <= budget_tokens * cfg.kv_bytes_per_token(2)
            slots[dt] = n
        assert slots["int8"] >= int(1.8 * slots[""])
        assert slots["int4"] >= 2 * slots[""]

    def test_no_budget_means_no_host_tier(self):
        cfg = get_config("llama2-13b")
        for dt in ("", "int8", "int4"):
            n, _ = host_tier_geometry(cfg, None, 128, dt)
            assert n == 0


class TestPagedKv:
    """The one host-side page book both execution backends hold: pool
    sizing, the token rules, and the release/retention rule for a row,
    tested on their own (the backend parity runs cover the loop)."""

    PAGE = 16

    def _kv(self, arch="yi-6b", cache_len=256, **kw):
        # smoke widths: a bf16 cache, so a page of the budget is a page
        return PagedKv(get_smoke_config(arch), self.PAGE, cache_len, **kw)

    def _row(self, rid, plen, mnt=4, sid=None):
        r = _req(rid, plen=plen, mnt=mnt)
        r.session_id = sid
        r.tokens = np.arange(1000 * rid, 1000 * rid + plen, dtype=np.int32)
        return r

    def test_pool_sizing_sets_the_trash_page_aside(self):
        kv = self._kv(pool_tokens=40 * self.PAGE)
        assert (kv.cap, kv.pages_per_seq) == (256, 16)
        assert kv.alloc.n_pages == 39                 # stated budget
        assert self._kv(default_tokens=2 * 256).alloc.n_pages == 31
        assert self._kv(default_pages=50).alloc.n_pages == 49
        # a stated budget wins over either default
        assert self._kv(pool_tokens=40 * self.PAGE,
                        default_tokens=4 * 256).alloc.n_pages == 39

    def test_default_pool_is_raised_to_one_context(self):
        # one slot's worth of tokens less the trash page: clamped, no error
        assert self._kv(default_tokens=256).alloc.n_pages == 16
        assert self._kv(default_pages=3).alloc.n_pages == 16

    def test_stated_pool_below_one_context_raises(self):
        with pytest.raises(ValueError, match="too small"):
            self._kv(pool_tokens=16 * self.PAGE)      # 15 pages + trash
        assert self._kv(pool_tokens=17 * self.PAGE).alloc.n_pages == 16

    def test_window_capped_insert_and_decode_tokens(self):
        # recurrentgemma's local window caps the per-request cache at 64
        kv = self._kv("recurrentgemma-2b")
        assert (kv.cap, kv.pages_per_seq) == (64, 4)
        assert kv.insert_tokens(_req(0, plen=10)) == 11
        assert kv.insert_tokens(_req(1, plen=100)) == 64
        r = _req(2, plen=10)
        r.generated = 5
        assert kv.decode_tokens(r) == 15
        r.generated = 80
        assert kv.decode_tokens(r) == 64

    def test_decode_tokens_subtract_promoted_slice_tokens(self):
        kv = self._kv()
        r = _req(0, plen=12)          # 10 prompt + 2 promoted by a slice
        r.generated, r.sliced_tokens = 5, 2
        assert kv.decode_tokens(r) == 15

    def test_transcript_excludes_promoted_slice_tokens(self):
        kv = self._kv()
        r = self._row(0, plen=12)
        gen = np.arange(500, 505, dtype=np.int32)
        r.generated, r.sliced_tokens = 5, 2
        # prompt, then generated[2:4]: [0:2] are inside the prompt, and
        # the last token's KV was never written
        path = kv.transcript(r, gen)
        assert path.tolist() == r.tokens.tolist() + [502, 503]
        r.tokens = None
        assert kv.transcript(r, gen) is None

    def test_first_token_only_row_is_freed_never_retained(self):
        kv = self._kv(pool_tokens=40 * self.PAGE, session_ttl=60.0)
        once = self._row(0, plen=3 * self.PAGE, mnt=1, sid=7)
        turn = self._row(1, plen=3 * self.PAGE, mnt=4, sid=8)
        assert kv.admit([once, turn]) == 2
        for r in (once, turn):
            kv.register_prompt(r)
        # only the decode-continuing row's full prompt pages are indexed
        assert len(kv.retention) == 3
        once.generated = 1
        kv.release(once, lambda r: np.zeros(r.generated, np.int32), 1.0)
        assert not kv.alloc.holds(0)
        assert 7 not in kv.retention.sessions
        turn.generated = 4
        kv.release(turn, lambda r: np.zeros(r.generated, np.int32), 1.0)
        assert 8 in kv.retention.sessions           # transcript retained
        assert kv.alloc.live_pages() > 0

    def test_release_without_retention_frees_outright(self):
        kv = self._kv(pool_tokens=40 * self.PAGE)
        r = self._row(0, plen=50)
        assert kv.admit([r]) == 1
        r.generated = 3
        kv.release(r, lambda _: pytest.fail("ids read without retention"),
                   1.0)
        assert kv.alloc.free_pages() == kv.alloc.n_pages

    def test_tick_does_nothing_without_retention(self):
        kv = self._kv(pool_tokens=40 * self.PAGE)
        r = self._row(0, plen=50)
        assert kv.admit([r]) == 1
        before = (kv.alloc.free_pages(), kv.alloc.table(0))
        kv.tick(1e9)
        assert kv.retention is None
        assert (kv.alloc.free_pages(), kv.alloc.table(0)) == before

    def test_prefix_skip_is_the_batch_minimum_hit(self):
        rows = [self._row(0, 40), self._row(1, 40)]
        rows[0].prefix_hit_tokens, rows[1].prefix_hit_tokens = 32, 16
        batch = FormedBatch(rows, 40)
        assert self._kv(prefix_cache=True).prefix_skip(batch) == 16
        assert self._kv().prefix_skip(batch) == 0

    def test_reset_empties_the_book_and_keeps_its_geometry(self):
        kv = self._kv(pool_tokens=40 * self.PAGE, prefix_cache=True)
        r = self._row(0, plen=2 * self.PAGE)
        assert kv.admit([r]) == 1
        kv.register_prompt(r)
        rt = kv.retention
        kv.reset()
        assert kv.alloc.n_pages == 39
        assert kv.alloc.free_pages() == 39
        assert kv.retention is not rt and len(kv.retention) == 0


class TestSharedPolicies:
    def test_admit_blocks_prefix(self):
        a = BlockAllocator(n_pages=3, page_size=8)
        reqs = [_req(0, 8), _req(1, 8), _req(2, 8), _req(3, 8)]
        n = admit_blocks(a, reqs, lambda r: r.prompt_len + 1)  # 2 pages each
        assert n == 1                                # second one doesn't fit
        assert a.holds(0) and not a.holds(1)

    def test_extend_preempts_youngest(self):
        a = BlockAllocator(n_pages=4, page_size=8)
        old = _req(0, plen=7, arrival=0.0)           # 1 page
        mid = _req(1, plen=7, arrival=1.0)
        yng = _req(2, plen=7, arrival=2.0)
        for r in (old, mid, yng):
            assert a.alloc(r.rid, r.prompt_len + 1) is not None
        # every request now needs a 2nd page; only 1 is free -> the
        # youngest loses its page so the older two can grow
        for r in (old, mid, yng):
            r.generated = 3                          # next write crosses
        victims = extend_for_decode(a, [old, mid, yng],
                                    lambda r: r.prompt_len + r.generated)
        assert victims == [yng]
        assert not a.holds(yng.rid)
        assert len(a.table(old.rid)) == 2
        assert len(a.table(mid.rid)) == 2

    def test_extend_no_preempt_when_pages_free(self):
        a = BlockAllocator(n_pages=8, page_size=8)
        r = _req(0, plen=7)
        a.alloc(0, 8)
        r.generated = 4
        assert extend_for_decode(a, [r], lambda q: q.prompt_len
                                 + q.generated) == []
        assert len(a.table(0)) == 2

    def test_starving_youngest_preempts_itself_not_an_elder(self):
        """Regression: when only the YOUNGEST request crosses a page
        boundary and no pages are free, it must evict itself — never an
        older request (which is closer to finishing)."""
        a = BlockAllocator(n_pages=2, page_size=8)
        old = _req(0, plen=7, arrival=0.0)           # 1 page, no growth
        yng = _req(1, plen=7, arrival=5.0)           # 1 page, will grow
        a.alloc(old.rid, 8)
        a.alloc(yng.rid, 8)
        yng.generated = 3                            # crosses the boundary
        old.generated = 0
        victims = extend_for_decode(
            a, [old, yng],
            lambda r: r.prompt_len + max(r.generated, 1))
        assert victims == [yng]
        assert a.holds(old.rid) and not a.holds(yng.rid)


# ----------------------------------------------------- property tests -----
if HAVE_HYPOTHESIS:
    ops = st.lists(
        st.one_of(
            st.tuples(st.just("alloc"), st.integers(0, 7),
                      st.integers(1, 200)),
            st.tuples(st.just("extend"), st.integers(0, 7),
                      st.integers(1, 200)),
            st.tuples(st.just("release"), st.integers(0, 7),
                      st.just(0)),
        ),
        min_size=1, max_size=60)

    class TestAllocatorProperties:
        @settings(deadline=None, max_examples=200)
        @given(ops=ops, n_pages=st.integers(1, 12),
               page=st.sampled_from([1, 8, 16, 128]))
        def test_random_interleavings_hold_invariants(self, ops, n_pages,
                                                      page):
            a = BlockAllocator(n_pages, page)
            tokens = {}
            for op, rid, tok in ops:
                if op == "alloc":
                    if a.holds(rid):
                        continue
                    if a.alloc(rid, tok) is not None:
                        tokens[rid] = tok
                elif op == "extend":
                    if not a.holds(rid):
                        continue
                    if a.extend(rid, tok) is not None:
                        tokens[rid] = max(tokens[rid], tok)
                else:
                    a.release(rid)
                    tokens.pop(rid, None)
                # never double-assign a page
                assigned = [p for r in tokens for p in a.table(r)]
                assert len(assigned) == len(set(assigned))
                # no leaks: free + live == total
                assert a.free_pages() + a.live_pages() == n_pages
                # tables cover exactly ceil(tokens / page) pages
                for r, tk in tokens.items():
                    assert len(a.table(r)) == -(-tk // page)

    shared_ops = st.lists(
        st.one_of(
            st.tuples(st.just("alloc"), st.integers(0, 7),
                      st.integers(1, 200)),
            # share the longest live prefix of a donor's table
            st.tuples(st.just("salloc"), st.integers(0, 7),
                      st.integers(1, 200), st.integers(0, 7)),
            st.tuples(st.just("extend"), st.integers(0, 7),
                      st.integers(1, 200)),
            st.tuples(st.just("release"), st.integers(0, 7)),
            st.tuples(st.just("rerelease"), st.integers(0, 7)),
            st.tuples(st.just("pin"), st.integers(0, 7)),
            st.tuples(st.just("unpin"), st.integers(0, 30)),
        ),
        min_size=1, max_size=80)

    class TestRefcountedAllocatorProperties:
        """Satellite (PR 3): the PR 2 invariants generalized to
        refcounted alloc/share/pin/release interleavings — no page is
        freed while referenced, free + unique-live == total, release is
        idempotent per rid.  A host-side refcount mirror is maintained
        independently and compared against the allocator every step."""

        @settings(deadline=None, max_examples=200)
        @given(ops=shared_ops, n_pages=st.integers(2, 14),
               page=st.sampled_from([1, 8, 128]))
        def test_refcounted_interleavings_hold_invariants(self, ops,
                                                          n_pages, page):
            a = BlockAllocator(n_pages, page)
            tables = {}                       # rid -> expected table
            pins = []                         # pages we pinned (with dups)
            for op in ops:
                kind, rid = op[0], op[1]
                if kind == "alloc" and not a.holds(rid):
                    t = a.alloc(rid, op[2])
                    if t is not None:
                        tables[rid] = t
                elif kind == "salloc" and not a.holds(rid):
                    donor = tables.get(op[3])
                    need = a.pages_for(op[2])
                    shared = (donor or [])[:need]
                    t = a.alloc(rid, op[2], shared=shared)
                    if t is not None:
                        assert t[:len(shared)] == list(shared)
                        tables[rid] = t
                elif kind == "extend" and a.holds(rid):
                    new = a.extend(rid, op[2])
                    if new is not None:
                        tables[rid].extend(new)
                elif kind == "release":
                    freed = a.release(rid)
                    t = tables.pop(rid, None)
                    assert (freed > 0) <= (t is not None)
                elif kind == "rerelease":
                    a.release(rid)
                    tables.pop(rid, None)
                    assert a.release(rid) == 0       # idempotent per rid
                elif kind == "pin" and a.holds(rid) and a.table(rid):
                    p = a.table(rid)[0]
                    a.pin(p)
                    pins.append(p)
                elif kind == "unpin" and pins:
                    a.unpin(pins.pop(op[1] % len(pins)))

                # refcount == (#tables holding the page) + (#pins)
                expect = {}
                for t in tables.values():
                    for p in t:
                        expect[p] = expect.get(p, 0) + 1
                for p in pins:
                    expect[p] = expect.get(p, 0) + 1
                for p in range(n_pages):
                    assert a.refs(p) == expect.get(p, 0)
                # no page freed while referenced; shared counted once
                assert a.free_pages() + a.live_pages() == n_pages
                assert a.live_pages() == len(expect)
                # tables still cover their spans exactly
                for rid2, t in tables.items():
                    assert a.table(rid2) == t


if HAVE_HYPOTHESIS:
    spill_ops = st.lists(
        st.one_of(
            st.tuples(st.just("alloc"), st.integers(0, 7),
                      st.integers(1, 200)),
            st.tuples(st.just("salloc"), st.integers(0, 7),
                      st.integers(1, 200), st.integers(0, 7)),
            st.tuples(st.just("extend"), st.integers(0, 7),
                      st.integers(1, 200)),
            st.tuples(st.just("release"), st.integers(0, 7)),
            st.tuples(st.just("pin"), st.integers(0, 7)),
            st.tuples(st.just("unpin"), st.integers(0, 30)),
            # host tier transitions (PR 5)
            st.tuples(st.just("spill"), st.integers(0, 30)),
            st.tuples(st.just("spill_shared"), st.integers(0, 7)),
            st.tuples(st.just("rbegin"), st.integers(0, 30)),
            st.tuples(st.just("rcommit"), st.integers(0, 30)),
            st.tuples(st.just("rdrop"), st.integers(0, 30)),
        ),
        min_size=1, max_size=100)

    class TestSpillRestoreProperties:
        """Satellite (PR 5): spill -> release -> restore -> pin
        orderings hold the extended invariants — a shared radix page's
        spill is refused while referenced, restore is idempotent, and
        free + unique-live + spilled == total across both tiers.  A
        host-side mirror (pins / spilled slots / restores in flight) is
        maintained independently and compared every step."""

        @settings(deadline=None, max_examples=200)
        @given(ops=spill_ops, n_pages=st.integers(2, 12),
               host_pages=st.integers(0, 6),
               page=st.sampled_from([1, 8, 128]))
        def test_spill_restore_interleavings_hold_invariants(
                self, ops, n_pages, host_pages, page):
            # deliberately asymmetric byte prices: device pages cost
            # 4x what a (compressed) host slot costs
            a = BlockAllocator(n_pages, page, host_pages=host_pages,
                               page_bytes=page * 4,
                               host_slot_bytes=page + 1)
            tables = {}                  # rid -> expected table
            pins = []                    # caller-held page pins (dups ok)
            spilled = []                 # caller-owned host slots at rest
            restoring = {}               # hslot -> reserved device page
            for op in ops:
                kind = op[0]
                if kind == "alloc" and not a.holds(op[1]):
                    t = a.alloc(op[1], op[2])
                    if t is not None:
                        tables[op[1]] = t
                elif kind == "salloc" and not a.holds(op[1]):
                    donor = tables.get(op[3])
                    shared = (donor or [])[:a.pages_for(op[2])]
                    t = a.alloc(op[1], op[2], shared=shared)
                    if t is not None:
                        tables[op[1]] = t
                elif kind == "extend" and a.holds(op[1]):
                    new = a.extend(op[1], op[2])
                    if new is not None:
                        tables[op[1]].extend(new)
                elif kind == "release":
                    a.release(op[1])
                    tables.pop(op[1], None)
                elif kind == "pin" and a.holds(op[1]) and a.table(op[1]):
                    p = a.table(op[1])[0]
                    a.pin(p)
                    pins.append(p)
                elif kind == "unpin" and pins:
                    a.unpin(pins.pop(op[1] % len(pins)))
                elif kind == "spill" and pins:
                    p = pins[op[1] % len(pins)]
                    h = a.spill(p)
                    in_table = any(p in t for t in tables.values())
                    if h is not None:
                        # only a sole-pin page with no table sharer spills
                        assert not in_table and pins.count(p) == 1
                        pins.remove(p)       # pin moved to the host slot
                        assert h not in spilled and h not in restoring
                        spilled.append(h)
                    else:
                        assert (in_table or pins.count(p) > 1
                                or not a.free_host_slots())
                elif kind == "spill_shared" and a.holds(op[1]):
                    # a page in a live table must NEVER spill
                    p = a.table(op[1])[0]
                    before = a.refs(p)
                    assert a.spill(p) is None
                    assert a.refs(p) == before
                elif kind == "rbegin" and spilled:
                    h = spilled[op[1] % len(spilled)]
                    pg = a.restore_begin(h)
                    if pg is not None:
                        assert a.restore_begin(h) == pg   # idempotent
                        spilled.remove(h)
                        restoring[h] = pg
                elif kind == "rcommit" and restoring:
                    h = list(restoring)[op[1] % len(restoring)]
                    pg = restoring.pop(h)
                    assert a.restore_commit(h) is True
                    assert a.restore_commit(h) is False   # idempotent
                    pins.append(pg)          # reserved page is ours now
                elif kind == "rdrop" and spilled:
                    h = spilled[op[1] % len(spilled)]
                    assert a.drop_spilled(h) is True
                    spilled.remove(h)

                # refcount == tables + pins + restore reservations
                expect = {}
                for t in tables.values():
                    for p in t:
                        expect[p] = expect.get(p, 0) + 1
                for p in pins:
                    expect[p] = expect.get(p, 0) + 1
                for p in restoring.values():
                    expect[p] = expect.get(p, 0) + 1
                for p in range(n_pages):
                    assert a.refs(p) == expect.get(p, 0)
                # two-tier accounting: no leaks on either side
                assert a.free_pages() + a.live_pages() == n_pages
                assert a.free_host_slots() + a.spilled_slots() \
                    == host_pages
                assert a.spilled_slots() == len(spilled) + len(restoring)
                # no host slot double-assigned
                assert len(set(spilled) | set(restoring)) \
                    == len(spilled) + len(restoring)
                # byte denomination follows the page/slot counts in
                # each tier's OWN prices (quantized spill accounting)
                assert a.device_bytes_in_use() \
                    == a.live_pages() * page * 4
                assert a.host_bytes_in_use() \
                    == (len(spilled) + len(restoring)) * (page + 1)
