"""Continuous-batching serving engine over the paged KV cache.

Host-side orchestration around two jit-compiled device functions (built by
``runtime/executor.py``):

  * a **chunked prefill** step — processes one fixed-shape prompt chunk
    ``(prefill_batch, prefill_chunk)`` for newly admitted requests, writing
    their K/V into the shared page pools (``base`` is a traced scalar, so
    every chunk of every batch reuses a single compilation), and
  * a **decode** step — advances all active lanes one token against the
    page pools.

Prefill is disaggregated from decode: queued requests are admitted in
batches, prefilled chunk-by-chunk between decode rounds, and dropped into
free decode lanes — the decode batch never waits for a prompt to be fed
token-by-token.  Slots are recycled as requests finish (EOS / max_new) and
their pages return to the pool, so total KV memory is bounded by pages
actually cached, not ``lanes * max_context``.

The page table (``page_table.py``) lives on the host as numpy arrays: every
allocation, the lanes' lengths and which lanes are active are decided there
with no device program and no device read.  Each step call receives what
it needs of the table as small host-to-device copies (decode: the tokens,
``page_rows`` and the lengths, -1 for lanes that do not step; prefill: the
admitted lanes' rows and prompt lengths).  The only device-to-host reads
are the ``argmax`` of each step's logits, one per decode round and one per
prefill chunk.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import recording_attention
from repro.models.common import ModelConfig
from repro.models.transformer import supports_paged_decode
from repro.runtime.executor import (make_paged_decode_step,
                                    make_paged_prefill_step)
from repro.runtime.sharding import ShardPolicy

from .metrics import RequestMetrics, ServeMetrics
from .page_table import PageManager, PageState


@dataclasses.dataclass
class ServeRequest:
    """One generation request with scheduling metadata."""

    rid: str
    prompt: List[int]
    max_new: int
    arrival_s: float = 0.0          # offset from engine start
    deadline_ms: float = 0.0        # per-token latency SLO (0 = none)
    tokens: List[int] = dataclasses.field(default_factory=list)  # generated
    done: bool = False


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static geometry of one engine instance."""

    page_size: int = 16
    n_pages: int = 256              # shared pool rows per layer
    decode_slots: int = 8           # continuous-batching lanes
    max_context: int = 256          # per-lane ceiling (pages_per_slot * psz)
    prefill_batch: int = 4          # prompts prefetched per prefill round
    prefill_chunk: int = 32         # tokens per prefill jit call
    eos_id: Optional[int] = None

    def __post_init__(self):
        if self.max_context % self.page_size:
            raise ValueError(
                f"max_context={self.max_context} must be a multiple of "
                f"page_size={self.page_size}")

    @property
    def pages_per_slot(self) -> int:
        return self.max_context // self.page_size


class ServingEngine:
    """Greedy continuous-batching server for dense / MoE decoder LMs."""

    def __init__(self, cfg: ModelConfig, params, mesh, ecfg: EngineConfig,
                 policy: Optional[ShardPolicy] = None):
        if not supports_paged_decode(cfg):
            raise NotImplementedError(
                f"paged serving does not support arch_type={cfg.arch_type!r}")
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        policy = policy or ShardPolicy(tp=False, zero=False)
        self.pm = PageManager(n_pages=ecfg.n_pages,
                              n_slots=ecfg.decode_slots,
                              page_size=ecfg.page_size,
                              pages_per_slot=ecfg.pages_per_slot)
        self._decode = make_paged_decode_step(
            cfg, mesh, policy, ecfg.decode_slots, ecfg.n_pages,
            ecfg.page_size, self.pm.pages_per_slot).fn
        self._prefill = make_paged_prefill_step(
            cfg, mesh, policy, ecfg.prefill_batch, ecfg.prefill_chunk,
            ecfg.n_pages, ecfg.page_size, self.pm.pages_per_slot).fn
        from repro.models.transformer import init_paged_state
        self.pools = init_paged_state(cfg, ecfg.n_pages, ecfg.page_size)
        self.state: PageState = self.pm.init()
        self.metrics = ServeMetrics()
        # decode attention the decode step resolved to, once traced
        self.decode_attn: Optional[str] = None
        # host-side per-slot bookkeeping
        self._slot_req: List[Optional[ServeRequest]] = \
            [None] * ecfg.decode_slots
        self._slot_rm: List[Optional[RequestMetrics]] = \
            [None] * ecfg.decode_slots

    # ---- host reads + page accounting ---------------------------------
    def _host(self, x) -> np.ndarray:
        """Read a device value on the host (one sync, counted)."""
        self.metrics.host_syncs += 1
        return np.asarray(x)

    def _any_active(self) -> bool:
        with self.metrics.span("page_table"):
            return bool(self.state.active.any())

    def pages_in_use(self) -> int:
        """Pool rows held, from what the host knows: each live lane holds
        the pages reserved for its prompt at admission, or those covering
        its cached tokens once decode has grown past them."""
        psz = self.ecfg.page_size
        return sum(-(-max(len(r.prompt), len(r.prompt) + len(r.tokens) - 1)
                     // psz) for r in self._slot_req if r is not None)

    # ---- admission + prefill --------------------------------------------
    def _free_slots(self) -> List[int]:
        with self.metrics.span("page_table"):
            return np.flatnonzero(~self.state.active).tolist()

    def _admit_batch(self, queue: Deque[ServeRequest], now: float
                     ) -> List[int]:
        """Claim slots + prompt pages for up to ``prefill_batch`` queued
        requests (arrival order); returns the admitted slot ids."""
        m = self.metrics
        with m.span("admit") as span:
            admitted: List[int] = []
            waited = 0.0
            free = self._free_slots()
            while (queue and free
                   and len(admitted) < self.ecfg.prefill_batch):
                req = queue[0]
                if req.arrival_s > now:    # sorted by arrival: rest is later
                    break
                if len(req.prompt) > self.ecfg.max_context:
                    raise ValueError(
                        f"request {req.rid!r}: prompt length "
                        f"{len(req.prompt)} exceeds "
                        f"max_context={self.ecfg.max_context}")
                slot = free[0]
                with m.span("page_table"):
                    st, ok = self.pm.admit(self.state, slot, len(req.prompt))
                if not ok:
                    break                  # pool full — retry next round
                self.state = st
                queue.popleft()
                free.pop(0)
                self._slot_req[slot] = req
                self._slot_rm[slot] = RequestMetrics(
                    rid=req.rid, arrival_s=req.arrival_s, admitted_s=now,
                    prompt_tokens=len(req.prompt),
                    deadline_ms=req.deadline_ms)
                waited += now - req.arrival_s
                admitted.append(slot)
            span.set(n=len(admitted), waited_ms=waited * 1e3)
        return admitted

    def _prefill_admitted(self, slots: List[int], t0: float) -> None:
        """Chunked prefill for the admitted slots; records TTFT and seeds
        each lane's first generated token."""
        ecfg, pm, m = self.ecfg, self.pm, self.metrics
        PB, S = ecfg.prefill_batch, ecfg.prefill_chunk
        reqs = [self._slot_req[s] for s in slots]
        plens = [len(r.prompt) for r in reqs]
        n_chunks = -(-max(plens) // S)
        with m.span("page_table"):
            rows = np.full((PB, pm.pages_per_slot), -1, np.int32)
            rows[:len(slots)] = self.state.page_rows[slots]
        rows_j = jnp.asarray(rows)
        prompt_len = np.zeros((PB,), np.int32)
        prompt_len[:len(slots)] = plens
        plen_j = jnp.asarray(prompt_len)
        for c in range(n_chunks):
            base = c * S
            with m.span("prefill", chunk=c, n=len(slots)):
                # host-padded prompt block (PB, S)
                block = np.zeros((PB, S), np.int32)
                for i, r in enumerate(reqs):
                    part = r.prompt[base:base + S]
                    block[i, :len(part)] = part
                logits, self.pools = self._prefill(
                    self.params, self.pools, jnp.asarray(block), rows_j,
                    jnp.int32(base), plen_j)
                first = self._host(jnp.argmax(logits, axis=-1))
            with m.span("bookkeep"):
                m.prefill_chunks += 1
                tnow = time.perf_counter() - t0
                for i, (slot, r) in enumerate(zip(slots, reqs)):
                    if base <= plens[i] - 1 < base + S:  # prompt ends here
                        r.tokens.append(int(first[i]))
                        rm = self._slot_rm[slot]
                        rm.first_token_s = tnow
                        rm.new_tokens = 1
        # lanes now hold their full prompt
        with m.span("page_table"):
            lengths = self.state.lengths.copy()
            lengths[slots] = plens
            self.state = self.state._replace(lengths=lengths)
        for slot, r in zip(slots, reqs):
            if r.max_new <= 1 or (self.ecfg.eos_id is not None
                                  and r.tokens[-1] == self.ecfg.eos_id):
                self._finish(slot, time.perf_counter() - t0)

    # ---- decode ----------------------------------------------------------
    def _finish(self, slot: int, tnow: float) -> None:
        m = self.metrics
        with m.span("bookkeep"):
            req, rm = self._slot_req[slot], self._slot_rm[slot]
            req.done = True
            rm.new_tokens = len(req.tokens)
            rm.finish_s = tnow
            m.requests.append(rm)
            self._slot_req[slot] = None
            self._slot_rm[slot] = None
            with m.span("page_table"):
                self.state = self.pm.free_slot(self.state, slot)

    def _decode_round(self, t0: float) -> None:
        """Advance every steppable lane one token."""
        m = self.metrics
        with m.span("page_table"):
            self.state, ok = self.pm.ensure_append_capacity(
                self.state, self.state.active)
            stuck = not ok.any() and bool(self.state.active.any())
            lengths = np.where(ok, self.state.lengths, np.int32(-1))
        if not ok.any():
            if stuck:
                raise RuntimeError(
                    "page pool exhausted: no active lane can append (grow "
                    "n_pages or lower decode_slots)")
            return
        with m.span("decode"):
            token = np.zeros((self.ecfg.decode_slots,), np.int32)
            for i, r in enumerate(self._slot_req):
                if r is not None and ok[i]:
                    token[i] = r.tokens[-1]
            with recording_attention() as seen:
                logits, self.pools = self._decode(
                    self.params, self.pools, jnp.asarray(token),
                    jnp.asarray(self.state.page_rows), jnp.asarray(lengths))
            if seen:                   # this call traced the decode step
                self.decode_attn = ",".join(sorted(seen))
            nxt = self._host(jnp.argmax(logits, axis=-1))
        with m.span("page_table"):
            self.state = self.pm.advance(self.state, ok)
        with m.span("bookkeep"):
            psz = self.ecfg.page_size
            m.decode_round(int(np.sum(-(-np.maximum(lengths, 0) // psz))),
                           self.state.page_rows.size)
            tnow = time.perf_counter() - t0
            for i in range(self.ecfg.decode_slots):
                if not ok[i]:
                    continue
                req = self._slot_req[i]
                req.tokens.append(int(nxt[i]))
                finished = (len(req.tokens) >= req.max_new
                            or (self.ecfg.eos_id is not None
                                and int(nxt[i]) == self.ecfg.eos_id))
                if finished:
                    self._finish(i, tnow)

    # ---- top level -------------------------------------------------------
    def run(self, requests: List[ServeRequest],
            verbose: bool = False) -> ServeMetrics:
        """Serve ``requests`` to completion; returns the metrics record.

        Requests are admitted in arrival order as lanes and pages free up;
        ``arrival_s`` is honored against the engine's wall clock (a request
        "arriving later" than the current elapsed time stays queued).
        Backend compiles during the run are counted in the metrics."""
        t0 = time.perf_counter()
        m = self.metrics
        queue: Deque[ServeRequest] = deque(
            sorted(requests, key=lambda r: r.arrival_s))
        jax.monitoring.register_event_duration_secs_listener(m.on_duration)
        try:
            while True:
                lanes = sum(r is not None for r in self._slot_req)
                with m.span("round", queue=len(queue), lanes=lanes):
                    if not (queue or self._any_active()):
                        break
                    self._round(queue, t0)
                if verbose:
                    done = sum(1 for r in requests if r.done)
                    print(f"[engine] done={done}/{len(requests)} "
                          f"queue={len(queue)} "
                          f"occ={self.pages_in_use() / self.ecfg.n_pages:.2f}")
        finally:
            jax.monitoring.unregister_event_duration_listener(m.on_duration)
        m.wall_s = time.perf_counter() - t0
        m.decode_attn = self.decode_attn
        return m

    def _round(self, queue: Deque[ServeRequest], t0: float) -> None:
        """One iteration of the engine loop: admit and prefill what is
        due, then one decode round (or wait for the next arrival)."""
        m = self.metrics
        now = time.perf_counter() - t0
        slots = self._admit_batch(queue, now)
        if slots:
            self._prefill_admitted(slots, t0)
        with m.span("bookkeep"):
            m.sample(len(queue), self.pages_in_use() / self.ecfg.n_pages)
        if self._any_active():
            self._decode_round(t0)
        elif queue:
            if queue[0].arrival_s <= now and not slots:
                raise RuntimeError(
                    f"request {queue[0].rid!r} cannot be admitted into "
                    f"an idle engine: prompt needs "
                    f"{-(-len(queue[0].prompt) // self.ecfg.page_size)} "
                    f"pages but the pool has {self.ecfg.n_pages} total "
                    "(grow n_pages)")
            # everything queued is in the future; idle until it lands
            with m.span("wait"):
                time.sleep(max(0.0, min(0.001, queue[0].arrival_s - now)))
