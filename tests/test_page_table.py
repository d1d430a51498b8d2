"""Paged KV-cache page-table invariants (repro.serving.page_table).

The PageManager is pure function-of-state over numpy arrays on the host:
every op returns a new PageState, and any array-like input (``jax`` arrays
included, as most tests here pass) is accepted.  These tests check the
allocator's accounting — no double allocation, exact free/used counts, rank-matched grants under
contention, graceful refusal when the pool is exhausted — all of which the
serving engine relies on for correctness (a double-granted page would
silently cross-contaminate two requests' KV).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import PageManager


def mk(n_pages=16, n_slots=4, page_size=8, pages_per_slot=4):
    return PageManager(n_pages=n_pages, n_slots=n_slots,
                       page_size=page_size, pages_per_slot=pages_per_slot)


def owners_consistent(pm, st):
    """page_owner and page_rows must agree exactly."""
    owner = np.asarray(st.page_owner)
    rows = np.asarray(st.page_rows)
    for slot in range(pm.n_slots):
        for p in rows[slot]:
            if p >= 0:
                assert owner[p] == slot, (slot, p, owner[p])
    for page, o in enumerate(owner):
        if o >= 0:
            assert page in rows[o], (page, o)


def test_init_all_free():
    pm = mk()
    st = pm.init()
    assert int(pm.free_pages(st)) == pm.n_pages
    assert int(pm.used_pages(st)) == 0
    assert float(pm.occupancy(st)) == 0.0
    assert not bool(jnp.any(st.active))


def test_admit_reserves_ceil_div_pages():
    pm = mk(page_size=8)
    st = pm.init()
    for plen, want in [(1, 1), (8, 1), (9, 2), (16, 2), (17, 3)]:
        st2, ok = pm.admit(st, 0, plen)
        assert bool(ok)
        assert int(pm.used_pages(st2)) == want
        assert int(st2.lengths[0]) == 0 and bool(st2.active[0])
        owners_consistent(pm, st2)


def test_admit_rollback_when_pool_too_small():
    pm = mk(n_pages=2, page_size=8, pages_per_slot=4)
    st = pm.init()
    st, ok = pm.admit(st, 0, 17)          # needs 3 pages, pool has 2
    assert not bool(ok)
    # full rollback: nothing allocated, slot not activated
    assert int(pm.used_pages(st)) == 0
    assert not bool(st.active[0])


def test_free_slot_returns_pages():
    pm = mk()
    st = pm.init()
    st, ok = pm.admit(st, 1, 20)
    assert bool(ok)
    used = int(pm.used_pages(st))
    assert used == 3
    st = pm.free_slot(st, 1)
    assert int(pm.used_pages(st)) == 0
    assert not bool(st.active[1])
    assert not bool(jnp.any(st.page_rows[1] >= 0))
    owners_consistent(pm, st)


def test_no_double_allocation_across_slots():
    pm = mk(n_pages=8, n_slots=4, page_size=8, pages_per_slot=2)
    st = pm.init()
    for slot in range(4):
        st, ok = pm.admit(st, slot, 16)   # 2 pages each -> exactly full
        assert bool(ok)
    owner = np.asarray(st.page_owner)
    assert (owner >= 0).all()             # pool exactly exhausted
    rows = np.asarray(st.page_rows)
    flat = rows[rows >= 0]
    assert len(set(flat.tolist())) == len(flat)   # all distinct pages
    owners_consistent(pm, st)


def test_ensure_append_capacity_rank_matching():
    """Three lanes hit a page boundary at once with only 2 free pages:
    exactly two rank-matched grants, the third lane is refused (not
    corrupted)."""
    pm = mk(n_pages=5, n_slots=3, page_size=4, pages_per_slot=4)
    st = pm.init()
    for slot in range(3):
        st, ok = pm.admit(st, slot, 4)    # 1 page each -> 2 pages free
        assert bool(ok)
    st = pm.advance(st, jnp.array([True, True, True]))  # len 1
    # jump to the boundary: next token needs a second page per lane
    st = st._replace(lengths=jnp.array([4, 4, 4], jnp.int32))
    want = jnp.array([True, True, True])
    st2, ok = pm.ensure_append_capacity(st, want)
    assert int(jnp.sum(ok)) == 2
    assert int(pm.free_pages(st2)) == 0
    owners_consistent(pm, st2)
    # the refused lane keeps its old single page, untouched
    refused = int(jnp.argmin(ok))
    assert int(jnp.sum(st2.page_rows[refused] >= 0)) == 1


def test_ensure_append_capacity_noop_mid_page():
    pm = mk(page_size=8)
    st = pm.init()
    st, _ = pm.admit(st, 0, 4)
    st = st._replace(lengths=jnp.array([2, 0, 0, 0], jnp.int32))
    before = int(pm.used_pages(st))
    st2, ok = pm.ensure_append_capacity(st, jnp.array([True, False, False,
                                                       False]))
    assert bool(ok[0])
    assert int(pm.used_pages(st2)) == before      # mid-page: nothing to do


def test_ensure_append_capacity_respects_max_context():
    pm = mk(n_pages=16, n_slots=2, page_size=4, pages_per_slot=2)  # max 8 tok
    st = pm.init()
    st, _ = pm.admit(st, 0, 4)
    st = st._replace(lengths=jnp.array([8, 0], jnp.int32))  # at the ceiling
    st2, ok = pm.ensure_append_capacity(st, jnp.array([True, False]))
    assert not bool(ok[0])                # cannot grow past pages_per_slot


class RefAllocator:
    """Plain-Python free-list allocator with the page table's semantics:
    the lowest free rows first, prompt pages at the lane's next unassigned
    logical pages, append pages to lanes in lane order."""

    def __init__(self, pm):
        self.pm = pm
        self.free = list(range(pm.n_pages))          # kept sorted
        self.rows = [[-1] * pm.pages_per_slot for _ in range(pm.n_slots)]
        self.lengths = [0] * pm.n_slots
        self.active = [False] * pm.n_slots

    def admit(self, slot, prompt_len):
        n = -(-prompt_len // self.pm.page_size)
        cur = sum(r >= 0 for r in self.rows[slot])
        if n > len(self.free) or cur + n > self.pm.pages_per_slot:
            return False
        for i in range(n):
            self.rows[slot][cur + i] = self.free.pop(0)
        self.lengths[slot], self.active[slot] = 0, True
        return True

    def free_slot(self, slot):
        self.free = sorted(self.free + [r for r in self.rows[slot]
                                        if r >= 0])
        self.rows[slot] = [-1] * self.pm.pages_per_slot
        self.lengths[slot], self.active[slot] = 0, False

    def ensure_append_capacity(self, want):
        ok = []
        for slot in range(self.pm.n_slots):
            li = self.lengths[slot] // self.pm.page_size
            if not (want[slot] and self.active[slot]) \
                    or li >= self.pm.pages_per_slot:
                ok.append(False)
            elif self.rows[slot][li] >= 0:
                ok.append(True)
            elif self.free:
                self.rows[slot][li] = self.free.pop(0)
                ok.append(True)
            else:
                ok.append(False)
        return ok

    def advance(self, stepped):
        self.lengths = [n + bool(s) for n, s in zip(self.lengths, stepped)]

    def owner(self):
        out = [-1] * self.pm.n_pages
        for slot, rows in enumerate(self.rows):
            for r in rows:
                if r >= 0:
                    out[r] = slot
        return out


def test_random_ops_match_free_list_reference():
    """A seeded random run of admits (each followed by the prefill's
    length update), append-capacity rounds, advances and frees, checked
    after every call against the plain free-list allocator: same ``ok``,
    same rows granted, consistent owners, no page held twice, exact
    counts.  The pool is small, so grants are refused and pages reused."""
    pm = mk(n_pages=12, n_slots=4, page_size=4, pages_per_slot=5)
    ref = RefAllocator(pm)
    st = pm.init()
    rng = np.random.default_rng(1234)
    last_ok = np.zeros(pm.n_slots, bool)
    refused = {"admit": 0, "append": 0}
    for _ in range(300):
        op = rng.choice(["admit", "append", "advance", "free"],
                        p=[0.25, 0.35, 0.3, 0.1])
        if op == "admit":
            slot = int(rng.integers(pm.n_slots))
            if ref.active[slot]:
                continue
            plen = int(rng.integers(1, pm.max_context + 1))
            st, ok = pm.admit(st, slot, plen)
            assert bool(ok) == ref.admit(slot, plen)
            if ok:                            # prefill fills the prompt
                lengths = np.asarray(st.lengths).copy()
                lengths[slot] = ref.lengths[slot] = plen
                st = st._replace(lengths=lengths)
            refused["admit"] += not ok
        elif op == "append":
            want = rng.random(pm.n_slots) < 0.8
            st, ok = pm.ensure_append_capacity(st, want)
            ref_ok = ref.ensure_append_capacity(want)
            assert np.asarray(ok).tolist() == ref_ok
            last_ok = np.array(ok, bool)
            refused["append"] += int(np.sum(want & ~last_ok
                                            & np.asarray(st.active)))
        elif op == "advance":
            st = pm.advance(st, last_ok)
            ref.advance(last_ok)
            last_ok = np.zeros(pm.n_slots, bool)
        else:
            slot = int(rng.integers(pm.n_slots))
            st = pm.free_slot(st, slot)
            ref.free_slot(slot)
            last_ok &= np.arange(pm.n_slots) != slot
        assert np.asarray(st.page_rows).tolist() == ref.rows
        assert np.asarray(st.page_owner).tolist() == ref.owner()
        assert np.asarray(st.lengths).tolist() == ref.lengths
        assert np.asarray(st.active).tolist() == ref.active
        owners_consistent(pm, st)
        rows = np.asarray(st.page_rows)
        held = rows[rows >= 0]
        assert len(set(held.tolist())) == len(held)
        assert int(pm.free_pages(st)) == len(ref.free)
        assert int(pm.used_pages(st)) == pm.n_pages - len(ref.free)
    assert refused["admit"] > 0 and refused["append"] > 0


def test_ops_stay_on_host():
    """Every op returns numpy fields and makes no device array: the
    engine's page-table work never dispatches a device program."""
    pm = mk(n_pages=6, n_slots=3, page_size=4, pages_per_slot=3)
    before = len(jax.live_arrays())
    out = []
    with jax.transfer_guard("disallow"):
        st = pm.init()
        out.append(st)
        st, ok = pm.admit(st, 0, 8)
        out += [st, ok]
        st, ok = pm.admit(st, 1, 12)
        out += [st, ok]
        st = st._replace(lengths=np.array([8, 3, 0], np.int32))
        st, ok = pm.ensure_append_capacity(st, st.active)
        out += [st, ok]
        st = pm.advance(st, ok)
        st = pm.free_slot(st, 1)
        st, ok = pm.reserve(st, 2, 2)
        out += [st, ok, pm.pages_needed(9), pm.free_pages(st),
                pm.used_pages(st), pm.occupancy(st)]
    for x in out:
        for leaf in (x if isinstance(x, tuple) else (x,)):
            assert not isinstance(leaf, jax.Array), type(leaf)
            assert isinstance(leaf, (np.ndarray, np.generic)), type(leaf)
    for st in (x for x in out if isinstance(x, tuple)):
        assert all(type(f) is np.ndarray for f in st)
        assert st.page_owner.dtype == st.page_rows.dtype == np.int32
        assert st.lengths.dtype == np.int32 and st.active.dtype == bool
    assert len(jax.live_arrays()) == before


def test_recycle_slot_reuses_pages():
    pm = mk(n_pages=4, n_slots=2, page_size=8, pages_per_slot=2)
    st = pm.init()
    st, ok = pm.admit(st, 0, 16)
    assert bool(ok) and int(pm.free_pages(st)) == 2
    st = pm.free_slot(st, 0)
    st, ok = pm.admit(st, 0, 16)          # recycled slot gets pages again
    assert bool(ok) and int(pm.free_pages(st)) == 2
    owners_consistent(pm, st)
