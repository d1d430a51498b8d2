"""Paged KV-cache bookkeeping: a functional page table over small numpy
arrays that live on the host.

The design follows the vLLM / maxtext ``page_manager`` idiom: one shared
pool of fixed-size pages per layer holds every lane's K/V, and a *single*
page table (shared by all layers — each layer indexes its own pool with the
same rows) maps (slot, logical page) -> pool row.  All state lives in
:class:`PageState`, a tuple of dense numpy arrays updated functionally; the
static geometry lives in :class:`PageManager`.  Allocation is rank
matching: the r-th lane (or logical page) that needs a page gets the r-th
free pool row, in row order.

The table stays on the host, as vLLM's block manager and JetStream keep
their block tables: it is a few KB, the host decides every allocation, and
nothing on the device reads it except as an argument of the jitted prefill
and decode steps.  Each op is a handful of numpy calls (microseconds) with
no device program and no device-to-host read; the engine copies
``page_rows`` and the lanes' lengths to the device once per step call.
Every op accepts any array-like state or mask (``np.asarray`` of it), so
``jax`` arrays work as inputs too.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import numpy as np


class PageState(NamedTuple):
    """Dense-array page table (numpy, on the host).

    ``page_owner`` (n_pages,) int32 — slot owning each pool row, -1 = free.
    ``page_rows`` (n_slots, pages_per_slot) int32 — pool row backing each
    lane's logical page, -1 = unassigned.
    ``lengths`` (n_slots,) int32 — tokens currently cached per lane (= the
    write position of the next token).
    ``active`` (n_slots,) bool — lane holds a live request.
    """

    page_owner: np.ndarray
    page_rows: np.ndarray
    lengths: np.ndarray
    active: np.ndarray


def _as_numpy(st: PageState) -> PageState:
    """``st`` with numpy fields (no copy when they already are)."""
    return PageState(np.asarray(st.page_owner, np.int32),
                     np.asarray(st.page_rows, np.int32),
                     np.asarray(st.lengths, np.int32),
                     np.asarray(st.active, bool))


@dataclasses.dataclass(frozen=True)
class PageManager:
    """Static geometry + pure page-table operations.

    ``n_pages`` pool rows of ``page_size`` tokens are shared by ``n_slots``
    decode lanes, each addressing at most ``pages_per_slot`` logical pages
    (so per-lane max context = pages_per_slot * page_size).  Methods take
    and return :class:`PageState`; none mutate their input.
    """

    n_pages: int
    n_slots: int
    page_size: int
    pages_per_slot: int

    def __post_init__(self):
        if min(self.n_pages, self.n_slots, self.page_size,
               self.pages_per_slot) < 1:
            raise ValueError("all PageManager dimensions must be >= 1")

    @property
    def max_context(self) -> int:
        return self.pages_per_slot * self.page_size

    def init(self) -> PageState:
        return PageState(
            page_owner=np.full((self.n_pages,), -1, np.int32),
            page_rows=np.full((self.n_slots, self.pages_per_slot), -1,
                              np.int32),
            lengths=np.zeros((self.n_slots,), np.int32),
            active=np.zeros((self.n_slots,), bool),
        )

    # ---- queries ---------------------------------------------------------
    def pages_needed(self, n_tokens) -> np.ndarray:
        """Pages required to hold ``n_tokens`` (ceil division)."""
        n = np.asarray(n_tokens, np.int32)
        return (n + self.page_size - 1) // self.page_size

    def free_pages(self, st: PageState) -> np.int32:
        return np.int32(np.count_nonzero(np.asarray(st.page_owner) < 0))

    def used_pages(self, st: PageState) -> np.int32:
        return np.int32(np.count_nonzero(np.asarray(st.page_owner) >= 0))

    def occupancy(self, st: PageState) -> np.float64:
        return self.used_pages(st) / self.n_pages

    # ---- allocation ------------------------------------------------------
    def reserve(self, st: PageState, slot, n_need
                ) -> Tuple[PageState, np.bool_]:
        """Assign the first ``n_need`` free pool rows to ``slot``'s next
        unassigned logical pages.  Returns ``(new_state, ok)``; on failure
        (not enough free rows, or the slot would exceed pages_per_slot)
        the state is returned unchanged and ``ok`` is False."""
        st = _as_numpy(st)
        slot, n_need = int(slot), int(n_need)
        free = np.flatnonzero(st.page_owner < 0)             # in row order
        cur = int(np.count_nonzero(st.page_rows[slot] >= 0))
        ok = np.bool_(len(free) >= n_need
                      and cur + n_need <= self.pages_per_slot)
        if not ok or n_need <= 0:
            return st, ok
        chosen = free[:n_need]
        rows = st.page_rows.copy()
        rows[slot, cur:cur + n_need] = chosen
        owner = st.page_owner.copy()
        owner[chosen] = slot
        return PageState(owner, rows, st.lengths, st.active), ok

    def admit(self, st: PageState, slot, prompt_len
              ) -> Tuple[PageState, np.bool_]:
        """Claim ``slot`` for a new request and reserve pages covering its
        ``prompt_len`` prompt tokens.  The lane starts at length 0 (prefill
        fills it); decode-time pages come from :meth:`ensure_append_capacity`.
        On failure nothing changes (full rollback) and ``ok`` is False.
        """
        slot = int(slot)
        st2, ok = self.reserve(st, slot, self.pages_needed(prompt_len))
        if not ok:
            return st2, ok
        lengths, active = st2.lengths.copy(), st2.active.copy()
        lengths[slot] = 0
        active[slot] = True
        return st2._replace(lengths=lengths, active=active), ok

    def free_slot(self, st: PageState, slot) -> PageState:
        """Release every page owned by ``slot`` and deactivate the lane."""
        st = _as_numpy(st)
        slot = int(slot)
        owner = np.where(st.page_owner == slot, np.int32(-1), st.page_owner)
        rows, lengths, active = (st.page_rows.copy(), st.lengths.copy(),
                                 st.active.copy())
        rows[slot] = -1
        lengths[slot] = 0
        active[slot] = False
        return PageState(owner, rows, lengths, active)

    def ensure_append_capacity(self, st: PageState, want
                               ) -> Tuple[PageState, np.ndarray]:
        """Guarantee each lane in ``want`` (n_slots, bool) has a page
        assigned for its next write position ``lengths[i]``.

        Multi-lane allocation: the lanes missing a page, in lane order, are
        matched with the free pool rows, in row order — rank r gets rank r.
        Returns ``(new_state, ok)`` with ``ok`` (n_slots,) False for lanes
        that could not get a page this round (pool exhausted or lane at
        pages_per_slot) — the engine skips those lanes for one step and
        retries after other requests release pages."""
        st = _as_numpy(st)
        want = np.asarray(want, bool) & st.active
        li = st.lengths // self.page_size                    # logical page
        li_c = np.clip(li, 0, self.pages_per_slot - 1)
        lanes = np.arange(self.n_slots)
        have = st.page_rows[lanes, li_c] >= 0
        fits = li < self.pages_per_slot
        need = np.flatnonzero(want & fits & ~have)
        free = np.flatnonzero(st.page_owner < 0)
        k = min(len(need), len(free))
        granted = np.zeros((self.n_slots,), bool)
        if k:
            lane, row = need[:k], free[:k]
            granted[lane] = True
            rows = st.page_rows.copy()
            rows[lane, li_c[lane]] = row
            owner = st.page_owner.copy()
            owner[row] = lane
            st = st._replace(page_owner=owner, page_rows=rows)
        return st, want & fits & (have | granted)

    def advance(self, st: PageState, stepped) -> PageState:
        """Bump ``lengths`` for lanes that wrote a token this step."""
        st = _as_numpy(st)
        return st._replace(
            lengths=st.lengths + np.asarray(stepped).astype(np.int32))
