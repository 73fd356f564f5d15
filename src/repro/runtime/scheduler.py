"""Continuous-batching request scheduler over the paged serving engine.

The engine's batch dimension is a SLOT POOL over one paged KV arena:
rows retire the moment they finish and queued prompts take their slots
between rounds (Orca-style iteration-level scheduling), so one long
generation never pins the batch and finished rows burn no decode steps.

Round
-----
``submit()`` enqueues a request (prompt + per-request ``max_new_tokens``
/ ``eos_id``); ``step()`` runs one scheduling round:

    admit (allocation only)  ->  extend / COW / sanitize the round's
    write spans  ->  ONE ``Engine.mixed_step`` dispatch  ->  advance
    prompt cursors, emit decode tokens, sample first tokens  ->  retire

Admission by allocation: admitting a request only ALLOCATES its row
(block table + ``lens`` cursor) from the host-side ``BlockPool``; no
model dispatch happens there.  Each request's worst-case block demand
is RESERVED at admission, so lazy per-round table extension can never
find the pool empty; when a reservation does not fit, admission defers
until retirements free blocks.  Retirement frees the row's blocks and
sentinels its table (``kvcache.paged_release_rows``).  Peak cache
memory is the blocks actually resident, not ``slots x max_len``.

Chunked prefill: the prompt flows through the decode lane in fixed
``chunk_size``-token chunks.  Every round issues ONE ``mixed_step``
dispatch that runs a prefill chunk for every prefilling row
(``T.prefill_chunk``, a no-op for rows with nothing to prefill) followed
by the masked decode quantum for every decoding row.  The compiled
shape depends only on ``(n_slots, chunk_size)`` — never on a prompt
length — so the engine compiles the serving loop ONCE and
``Engine.n_compiles`` stays flat however ragged the prompts are
(``tests/test_scheduler.py``).  A row that completes its prompt mid-
round samples its first token from that chunk's last-valid-position
logits and decodes from the next round on.  Greedy streams are
bitwise-identical to isolated ``Engine.generate``: ``prefill_chunk``
pads the KV length to fixed ``attn_chunk_kv`` blocks so the online-
softmax reduction groups identically for every split of a prompt (see
``models/layers.py``).  Inactive rows ride along in the batched decode
with frozen ``lens`` and their sampled tokens are discarded.

EDF with preemption: ``submit(..., deadline=...)`` attaches an absolute
sim-step deadline; admission is earliest-deadline-first (deadline-less
requests sort last, FIFO among equals, so with no deadlines the order
is plain FIFO).  When the EDF head cannot be admitted for lack of
blocks, the scheduler PREEMPTS the active row with the LATEST deadline
— only if strictly later than the candidate's, so best-effort never
preempts best-effort and livelock is impossible — by releasing its
blocks (owned blocks are freed, borrowed prefix blocks decref'd, the
prefix index keeps registered blocks resident) and requeueing the
request from scratch.  Greedy decode makes the restart token-identical
to an uninterrupted run; under ``sanitize`` the released blocks are
poisoned and the leak gauge stays zero.

Prefix caching (``prefix_cache=True``) deduplicates shared prompt
prefixes: every fully-written prompt block is content-addressed in a
:class:`kvcache.PrefixIndex` (rolling hash of its token ids, chained so
a hash identifies the whole prefix up to that block), and admission
first walks the index — matched leading blocks are BORROWED
(``BlockPool.share``) and the chunk cursor starts after them
(``min(matched * block_size, plen - 1)``: at least the last prompt
token reruns because its logits seed the first sampled token).  Writes
never land in a shared block: admission copy-on-writes the matched
blocks the remaining chunks overlap, the per-round write tables
sentinel every still-borrowed entry, and a pre-round pass COWs
window-lane ring slots about to recycle a shared block.  The index
holds one pool reference per registered block so prefixes outlive
their owner; index-only blocks (refcount 1) are evicted LRU-first when
admission needs capacity.  A sharer's reservation is ``worst - owned``
minus the dense-lane borrowed blocks append-only writes never touch;
window rows pre-reserve one COW per ring slot they may register.
Greedy streams match the non-sharing path (``tests/test_prefix.py``)
when the KV storage dtype is the compute dtype; with a posit KV codec
the borrowed prefix is read back through the codec, so logits past the
prefix can differ in the last ulp from a from-scratch prefill's.

Sampling: greedy decoding is deterministic and token-identical to
isolated generation.  With ``temperature > 0`` the PRNG stream
interleaves rows differently than isolated calls would, so per-request
identity only holds for greedy.

Time is measured in *decode steps* (the simulation clock, ``steps_run``):
deadlines and request latencies are in steps; real per-round wall time
is in the ``sched.round`` spans.
"""
from __future__ import annotations

import dataclasses
import itertools
from collections import deque
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.compress import kvcache as kvc
from repro.models import layers as L
from repro.models import transformer as T
from . import spans
from .engine import Engine, sample_token

_SCHED_IDS = itertools.count(1)   # the ``sched`` id of each instance


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    max_new_tokens: int
    eos_id: Optional[int] = None
    arrival_step: int = 0          # simulation clock at submit()
    deadline: Optional[int] = None  # absolute sim-step SLO (None = none)


@dataclasses.dataclass
class Completion:
    rid: int
    prompt_len: int
    tokens: np.ndarray             # (n,) int32, truncated at EOS/max_new
    arrival_step: int              # when the request was submitted
    admitted_step: int             # decode-step clock at admission
    finished_step: int             # decode-step clock when retired
    # the (V,) f32 logits the first token was sampled from, what a
    # one-shot prefill of the prompt is compared to
    first_logits: Optional[np.ndarray] = None

    @property
    def latency_steps(self) -> int:
        return self.finished_step - self.arrival_step

    @property
    def queue_steps(self) -> int:
        return self.admitted_step - self.arrival_step


@dataclasses.dataclass
class _Slot:
    req: Request
    emitted: list
    admitted_step: int
    done: bool = False
    # prompt positions already cached, or None once the whole prompt
    # is in
    cursor: Optional[int] = None
    first_logits: Optional[np.ndarray] = None

    @property
    def lens(self) -> int:
        """Row's cache occupancy: the chunk cursor while prefilling,
        else prompt + generated-so-far minus the not-yet-cached last
        token (mirrors the device ``lens`` entry)."""
        if self.cursor is not None:
            return self.cursor
        return len(self.req.prompt) + len(self.emitted) - 1


class Scheduler:
    """Iteration-level (continuous) batching over a paged :class:`Engine`.

    ``n_slots`` is the pool width (the compiled batch size),
    ``chunk_size`` both the decode steps between scheduling decisions
    and the prefill chunk width.  Larger chunks amortize host work;
    smaller chunks admit/retire sooner.  ``prefix_cache=True`` switches
    on content-addressed prefix sharing with copy-on-write block tables
    — see the module docstring for the full contract.
    """

    def __init__(self, engine: Engine, *, n_slots: int,
                 chunk_size: int = 8, eos_id: Optional[int] = None,
                 prefix_cache: bool = False,
                 chunked_prefill: bool = True):
        if engine.cfg.family != "transformer":
            raise ValueError(
                "continuous batching needs per-row decode positions, "
                "which only the transformer family provides (got family="
                f"{engine.cfg.family!r}); hymba/rwkv/whisper decode at a "
                "shared absolute position")
        if not engine.paged:
            raise ValueError(
                "the scheduler serves from a paged block arena: construct "
                "the engine with Engine(paged=True)")
        # accepted only for bench/program.py; goes with its next change
        if not chunked_prefill:
            raise ValueError(
                "chunked_prefill=False: the scheduler has one mode, "
                "chunked prefill through Engine.mixed_step")
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.engine = engine
        self.n_slots = int(n_slots)
        self.chunk_size = int(chunk_size)
        self.eos_id = eos_id
        self.prefix_cache = bool(prefix_cache)
        # arena sanitizer: inherited from the engine so one flag arms
        # both halves (host-side BlockPool checks + device poisoning)
        self.sanitize = engine.sanitize
        self.block_size = engine.block_size
        self.table_width = engine.table_width
        self.n_blocks = engine.n_blocks or self.n_slots * self.table_width
        self.pool = kvc.BlockPool(self.n_blocks, sanitize=self.sanitize)
        # tensor-parallel engines place the arena head-sharded over
        # 'model' here; one logical block id names one slice per shard,
        # so the host-side pool/table bookkeeping below is identical
        # with or without a mesh
        self.cache = engine.shard_cache(T.init_paged_cache(
            engine.cfg, self.n_slots, engine.max_len, self.block_size,
            self.n_blocks))
        self._window = T._paged_window(engine.cfg)
        self._tables = np.full(
            (self.n_slots, self.table_width), self.n_blocks, np.int32)
        self._row_blocks: list = [[] for _ in range(self.n_slots)]
        # borrowed table entries: slot index -> shared block id; the row
        # holds one pool reference per entry but must COW before ever
        # writing through it (empty unless prefix_cache)
        self._row_borrowed: list = [{} for _ in range(self.n_slots)]
        self._row_used = [0] * self.n_slots   # populated table slots
        self._worst = [0] * self.n_slots
        self._outstanding = 0          # reserved-but-unallocated blocks
        # high-water mark of PHYSICAL allocated + reserved blocks: an
        # arena of this size replays the same trace with zero deferrals
        # (the capacity-planning number).  peak_logical is the same mark
        # counting every reference — what a non-sharing pool would have
        # needed; the gap is the prefix-dedup win.
        self.peak_committed = 0
        self.peak_logical = 0
        # window+prefix rows reserve their registration COW head at
        # admission; settled when the fully-written prompt registers
        self._head_reserved = [0] * self.n_slots
        if self.prefix_cache:
            self.index = kvc.PrefixIndex()
        self._release = jax.jit(kvc.paged_release_rows)
        # prefix-caching observability (stay zero without prefix_cache)
        self.prefill_tokens = 0        # tokens actually run through prefill
        self.prefix_hits = 0           # admissions that borrowed blocks
        self.prefix_matched_tokens = 0  # prompt tokens served from cache
        self.n_cow = 0                 # copy-on-write block duplications
        self.n_evicted = 0             # index blocks reclaimed under pressure
        # sanitizer leak gauge: allocated blocks unreachable from any
        # live row, borrowed reference, or the prefix index, recomputed
        # at every retirement (0 on a healthy trace; see leak_report)
        self.n_leaked = 0
        self._slots: list = [None] * self.n_slots
        self._queue: deque = deque()
        self._cur_tok = np.zeros((self.n_slots,), np.int32)
        self._next_rid = 0
        self.steps_run = 0             # decode steps executed (sim clock)
        # spans (runtime/spans.py): this instance's id, the round
        # count, and each request's open lifecycle span (rid -> span)
        self.sched_id = next(_SCHED_IDS)
        self._round = 0
        self._life: dict = {}
        self.n_chunks = 0
        self.n_admitted = 0
        self.n_retired = 0
        self.n_preempted = 0           # rows evicted for an earlier deadline

    # ------------------------------------------------------------------
    # queue
    # ------------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *,
               eos_id: Optional[int] = None,
               deadline: Optional[int] = None) -> int:
        """Enqueue a request; returns its request id.

        ``deadline``: absolute sim-step (``steps_run`` clock) the
        request should finish by.  Deadlines drive EDF admission and
        preemption (see the module docstring); ``None`` marks the
        request best-effort — it sorts after every deadline and is the
        first preemption victim.

        Raises up front if the request could never fit: a row may need
        ``prompt + max_new - 1`` cache slots plus a full chunk of
        frontier headroom (a row can overshoot its stopping point by up
        to ``chunk_size - 1`` steps before retirement is detected).
        """
        prompt = [int(t) for t in prompt]
        max_new_tokens = int(max_new_tokens)
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        need = len(prompt) + max_new_tokens - 1 + self.chunk_size
        if need > self.engine.max_len:
            raise ValueError(
                f"request needs up to {need} cache slots (prompt "
                f"{len(prompt)} + {max_new_tokens} new + chunk "
                f"{self.chunk_size} headroom) > engine max_len "
                f"{self.engine.max_len}")
        worst = self._worst_blocks(len(prompt), max_new_tokens)
        if self.prefix_cache and self.engine.window_lane and \
                self._share_cap(len(prompt)):
            # registered ring blocks each pre-reserve one COW copy
            worst += len(prompt) // self.block_size
        if worst > self.n_blocks:
            raise ValueError(
                f"request needs up to {worst} cache blocks > block "
                f"pool capacity {self.n_blocks} (block_size "
                f"{self.block_size})")
        rid = self._next_rid
        self._next_rid += 1
        t = spans.event("request.submit", sched=self.sched_id,
                        rid=rid).start_ns
        self._life[rid] = spans.begin("request.queued", start_ns=t,
                                      sched=self.sched_id, rid=rid)
        self._queue.append(Request(rid=rid, prompt=prompt,
                                   max_new_tokens=max_new_tokens,
                                   eos_id=self.eos_id if eos_id is None
                                   else eos_id,
                                   arrival_step=self.steps_run,
                                   deadline=None if deadline is None
                                   else int(deadline)))
        return rid

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or any(
            s is not None for s in self._slots)

    @property
    def n_active(self) -> int:
        return sum(1 for s in self._slots if s is not None and not s.done)

    def progress(self) -> dict:
        """``rid -> (tokens delivered, cache positions written)`` for
        every request holding a slot, as of the last round's end: the
        tokens its slot has emitted, and its cache occupancy (the chunk
        cursor while it prefills).  A request that finished is in the
        completions of the round that retired it instead."""
        return {s.req.rid: (len(s.emitted), s.lens)
                for s in self._slots if s is not None}

    @property
    def stats(self) -> dict:
        """Counters for one serving run — notably ``n_compiles``, the
        engine's distinct-lowered-program count, flat after warmup
        whatever the prompt lengths.
        ``step_wall_p50_ms``/``step_wall_p99_ms`` are REAL per-round
        wall times (0.0 before the first round), read from this
        scheduler's ``sched.round`` spans still in the recorder's ring;
        the sim clock (``steps_run``) stays the scheduling time base."""
        wall = np.asarray(
            [s.end_ns - s.start_ns for s in spans.snapshot()
             if s.name == "sched.round"
             and s.ids["sched"] == self.sched_id], np.float64) * 1e-6
        return dict(
            n_admitted=self.n_admitted, n_retired=self.n_retired,
            n_preempted=self.n_preempted, n_chunks=self.n_chunks,
            steps_run=self.steps_run,
            step_wall_p50_ms=float(np.percentile(wall, 50))
            if wall.size else 0.0,
            step_wall_p99_ms=float(np.percentile(wall, 99))
            if wall.size else 0.0,
            prefill_tokens=self.prefill_tokens,
            prefix_hits=self.prefix_hits,
            prefix_matched_tokens=self.prefix_matched_tokens,
            n_cow=self.n_cow, n_evicted=self.n_evicted,
            n_leaked=self.n_leaked,
            n_compiles=self.engine.n_compiles,
            peak_committed=self.peak_committed,
            peak_logical=self.peak_logical)

    # ------------------------------------------------------------------
    # scheduling round
    # ------------------------------------------------------------------

    # -- paged block accounting ----------------------------------------

    def _worst_blocks(self, prompt_len: int, max_new: int) -> int:
        """Upper bound on the blocks a request can ever hold at once —
        reserved at admission so lazy per-chunk extension NEVER finds
        the pool empty.  Same formula the engine allocates by: a row
        can overshoot its stopping point by up to a full chunk."""
        return self.engine._row_blocks_needed(
            prompt_len, max_new - 1 + self.chunk_size)

    def _share_cap(self, plen: int) -> bool:
        """Is a prompt of ``plen`` tokens eligible for prefix sharing /
        registration?  The window lane shares only prompts that fit the
        window: then every logical block is resident at its identity
        ring slot, so donor and sharer agree on the slot -> position
        mapping (ring recycling of shared blocks is handled by the
        pre-chunk COW pass)."""
        if not self._window:
            return True
        return plen <= min(self.engine.max_len, self._window)

    def _evictable_count(self, exclude=()) -> int:
        """Index blocks whose ONLY reference is the index's — physical
        capacity admission may reclaim (minus ``exclude``: the blocks
        the current match is about to pin)."""
        ex = {int(i) for i in exclude}
        return sum(1 for b in self.index.blocks_lru()
                   if b not in ex and self.pool.refcount(b) == 1)

    def _take_blocks(self, n: int) -> list:
        """``pool.alloc(n)``, evicting least-recently-matched index-only
        blocks first if the free list is short.  Callers have already
        checked ``n_free + evictable`` covers their reservation."""
        if n > self.pool.n_free:
            evicted = []
            for bid in self.index.blocks_lru():
                if self.pool.n_free >= n:
                    break
                if self.pool.refcount(bid) == 1:
                    self.index.pop_block(bid)
                    evicted += self.pool.free([bid])
                    self.n_evicted += 1
            if self.sanitize and evicted:
                # evicted blocks may linger on the free list past the
                # alloc below — poison them so any stale index/table
                # path that still names them reads garbage, loudly
                self.cache = self.engine.poison_blocks(self.cache, evicted)
        return self.pool.alloc(n)

    def _match_prefix(self, prompt) -> list:
        """Longest chain of resident index blocks covering the prompt's
        leading full blocks; returns their physical ids."""
        ids = []
        for h in kvc.prefix_block_hashes(prompt, self.block_size):
            bid = self.index.get(h)
            if bid is None:
                break
            ids.append(int(bid))
        return ids

    def _register_row(self, prompt, row: int):
        """Content-address this row's fully-written prompt blocks.  Each
        newly registered block gets one extra pool reference HELD BY THE
        INDEX, so the prefix outlives the row; window rows additionally
        grow their reservation by one block per registration, because
        ring recycling will COW each shared slot at most once (chunked
        admission pre-reserved ``_head_reserved`` blocks for this —
        settle against it)."""
        plen = len(prompt)
        reserved, self._head_reserved[row] = self._head_reserved[row], 0
        n_reg = 0
        if self._share_cap(plen):
            for i, h in enumerate(kvc.prefix_block_hashes(
                    prompt, self.block_size)):
                if self.index.get(h) is not None:
                    continue           # first writer wins
                bid = int(self._tables[row, i])
                if bid == self.n_blocks:
                    continue
                self.index.put(h, bid)
                self.pool.share([bid])
                n_reg += 1
        if self.engine.window_lane and (n_reg or reserved):
            self._worst[row] += n_reg - reserved
            self._outstanding += n_reg - reserved

    def _row_debt(self, row: int) -> int:
        """Blocks still reserved (but not yet drawn) for a live row:
        worst-case minus owned.  Dense-lane borrowed entries are
        excluded — append-only decode can never write into a block that
        lies wholly before the suffix, so they need no COW reserve;
        window-lane borrowed entries keep theirs (ring recycling COWs
        each at most once)."""
        debt = self._worst[row] - len(self._row_blocks[row])
        if not self.engine.window_lane:
            debt -= len(self._row_borrowed[row])
        return debt

    def _note_peaks(self):
        # physical commitment excludes index-only blocks: those are
        # droppable cache (_take_blocks evicts them on demand), so an
        # arena of peak_committed still replays the trace deferral-free
        evictable = self._evictable_count() if self.prefix_cache else 0
        self.peak_committed = max(
            self.peak_committed,
            self.pool.in_use - evictable + self._outstanding)
        self.peak_logical = max(
            self.peak_logical,
            self.pool.logical_in_use + self._outstanding)

    def _admit_row(self, req: Request, row: int):
        """Admission: ALLOCATE only — block table, ``lens``
        cursor, prefix borrows.  No model dispatch happens here; the
        prompt flows through ``mixed_step`` chunks in subsequent
        rounds.  Returns the starting chunk cursor (0, or past the
        borrowed prefix on a hit), or ``None`` if the pool cannot cover
        the reservation yet."""
        plen = len(req.prompt)
        bs = self.block_size
        worst = self._worst_blocks(plen, req.max_new_tokens)
        matched, suffix_start = [], 0
        if self.prefix_cache and self._share_cap(plen):
            matched = self._match_prefix(req.prompt)
            # matched blocks skip their chunks entirely; at least the
            # last prompt token reruns — its logits seed tok0
            suffix_start = min(len(matched) * bs, plen - 1)
        # reservation check: COW/extension draws must never find the
        # pool empty.  Under prefix caching, index-only blocks count as
        # available — _take_blocks evicts them on demand; window rows
        # additionally pre-reserve one COW per block they may register
        # (settled at registration time, when the prompt is written).
        head = plen // bs if (
            self.prefix_cache and self.engine.window_lane and
            self._share_cap(plen)) else 0
        avail = self.pool.n_free + (
            self._evictable_count(exclude=matched)
            if self.prefix_cache else 0)
        if avail - self._outstanding < worst + head:
            return None                # wait for retirements' blocks
        used = self.table_width if self.engine.window_lane else \
            -(-plen // bs)
        block_ids = np.full((self.table_width,), self.n_blocks, np.int32)
        borrowed = {}
        if matched and suffix_start > 0:
            cow_from = suffix_start // bs  # first slot chunks write
            n_borrow = min(len(matched), cow_from)
            # pin the whole match BEFORE any eviction can reclaim it
            self.pool.share(matched)
            cow_slots = list(range(cow_from, len(matched)))
            fresh = self._take_blocks(
                used - len(matched) + len(cow_slots))
            block_ids[:len(matched)] = matched
            for s, nid in zip(cow_slots, fresh[:len(cow_slots)]):
                block_ids[s] = nid
            block_ids[len(matched):used] = fresh[len(cow_slots):]
            if cow_slots:
                # duplicate the pattern leaves block-for-block, then
                # drop our reference to the shared originals (the index
                # keeps them resident for future matches)
                self.cache = self.engine.copy_blocks(
                    self.cache, [matched[s] for s in cow_slots],
                    fresh[:len(cow_slots)])
                self.pool.release([matched[s] for s in cow_slots])
                self.n_cow += len(cow_slots)
            borrowed = {s: int(matched[s]) for s in range(n_borrow)}
            self.prefix_hits += 1
            self.prefix_matched_tokens += suffix_start
        else:
            suffix_start = 0
            fresh = self._take_blocks(used) if self.prefix_cache \
                else self.pool.alloc(used)
            block_ids[:used] = fresh
        self._tables[row] = block_ids
        self.cache = dict(
            self.cache,
            block_tables=jnp.asarray(self._tables),
            lens=jnp.asarray(self.cache["lens"],
                             jnp.int32).at[row].set(suffix_start))
        self._row_blocks[row] = list(fresh)
        self._row_borrowed[row] = borrowed
        self._row_used[row] = used
        self._worst[row] = worst
        self._head_reserved[row] = head
        self._worst[row] += head       # reserve the registration COWs
        self._outstanding += self._row_debt(row)
        self._note_peaks()
        return suffix_start

    def _write_span(self, slot):
        """Inclusive logical block range ``[lo, hi]`` the next round's
        writes may touch for this slot: the imminent prefill chunk while
        the cursor is live, else the decode quantum.  ``None`` if the
        round writes nothing for it."""
        bs = self.block_size
        if slot.cursor is not None:    # prefilling: this round's chunk
            n = min(self.chunk_size, len(slot.req.prompt) - slot.cursor)
            if n <= 0:
                return None
            return slot.cursor // bs, (slot.cursor + n - 1) // bs
        lo = slot.lens
        return lo // bs, (lo + self.chunk_size - 1) // bs

    def _cow_window_rows(self) -> bool:
        """Pre-chunk COW pass (window lane + prefix_cache only): the
        ring recycles blocks in place, so the next round's writes may
        land in blocks that are shared (borrowed from a donor, or this
        row's own registered prefix).  Duplicate each such block and
        swap the table entry first; the admission-time reservation
        covers every copy."""
        src, dst = [], []
        w = self.table_width
        for i, slot in enumerate(self._slots):
            if slot is None or slot.done:
                continue
            span = self._write_span(slot)
            if span is None:
                continue
            lo, hi = span
            for q in range(lo, hi + 1):
                s = q % w
                bid = int(self._tables[i, s])
                if bid == self.n_blocks or self.pool.refcount(bid) <= 1:
                    continue
                nid, = self._take_blocks(1)
                src.append(bid)
                dst.append(nid)
                self._tables[i, s] = nid
                self._row_blocks[i].append(nid)
                self._outstanding -= 1
                if self._row_borrowed[i].pop(s, None) is None:
                    # own registered block: index keeps the original
                    self._row_blocks[i].remove(bid)
                self.pool.release([bid])
                self.n_cow += 1
        if src:
            self.cache = self.engine.copy_blocks(self.cache, src, dst)
            return True
        return False

    def _ensure_blocks(self):
        """Extend each live dense row's table to cover the next chunk's
        writes (window rows never grow: their ring recycles in place —
        but under prefix caching recycled SHARED blocks are first
        duplicated by the COW pass).  The admission-time reservation
        guarantees the pool can serve this."""
        changed = False
        for i, slot in enumerate(self._slots):
            if slot is None or slot.done or self.engine.window_lane:
                continue
            if slot.cursor is not None:
                continue               # prompt blocks were allocated whole
            need = -(-min(slot.lens + self.chunk_size,
                          self.engine.max_len) // self.block_size)
            have = self._row_used[i]
            if need > have:
                ids = self._take_blocks(need - have) if self.prefix_cache \
                    else self.pool.alloc(need - have)
                self._tables[i, have:need] = ids
                self._row_blocks[i].extend(ids)
                self._row_used[i] = need
                self._outstanding -= len(ids)
                changed = True
        if self.prefix_cache and self.engine.window_lane:
            changed |= self._cow_window_rows()
        if changed:
            self.cache = dict(self.cache,
                              block_tables=jnp.asarray(self._tables))

    def _sanitize_check_chunk(self):
        """Pre-chunk sanitizer gate (``sanitize=True`` only): every
        resident table entry of a live row must still be allocated
        (``check_read`` — stale entries are use-after-free gathers) and
        every block the imminent round writes through must be
        exclusively owned (``check_write`` — refcount > 1 here means a
        COW pass was skipped and the write would corrupt every other
        owner's KV).  The write span mirrors ``_cow_window_rows``
        (``_write_span``: the prefill chunk while the cursor is live,
        the decode quantum after), mapped through the ring on the
        window lane."""
        w = self.table_width
        for i, slot in enumerate(self._slots):
            if slot is None or slot.done:
                continue
            row = self._tables[i]
            self.pool.check_read(
                int(b) for b in row if int(b) != self.n_blocks)
            span = self._write_span(slot)
            if span is None:
                continue
            lo, hi = span
            if self.engine.window_lane:
                slots_touched = {q % w for q in range(lo, hi + 1)}
            else:
                slots_touched = range(lo, min(hi, w - 1) + 1)
            self.pool.check_write(
                int(row[s]) for s in slots_touched
                if int(row[s]) != self.n_blocks)

    # -- policy: EDF ordering + preemption ------------------------------

    def _order_queue(self):
        """Earliest-deadline-first admission order (stable, so FIFO
        among equal deadlines and deadline-less requests).  With no
        deadlines in the queue this is a no-op — the PR 6 traces stay
        schedule-identical."""
        if any(r.deadline is not None for r in self._queue):
            self._queue = deque(sorted(
                self._queue,
                key=lambda r: float("inf") if r.deadline is None
                else r.deadline))

    def _preempt_row(self, i: int):
        """Evict a live row to free its blocks: drop every reference
        (owned blocks free, borrowed prefix blocks decref — the index
        keeps registered blocks resident), sentinel the table, zero the
        device ``lens``, and requeue the request from scratch.  Greedy
        decode makes the restart token-identical to an uninterrupted
        run; already-emitted tokens are discarded."""
        slot = self._slots[i]
        self._slots[i] = None
        self.n_preempted += 1
        self._phase(slot.req.rid, "request.queued", preempted=1)
        reclaimed = self._drop_row(i)
        mask = np.zeros((self.n_slots,), bool)
        mask[i] = True
        self._release_rows(mask, reclaimed)
        self._queue.append(slot.req)   # original arrival_step preserved

    def _drop_row(self, i: int) -> list:
        """Drop row ``i``'s block references: owned blocks physically
        reclaim unless the prefix index still holds them, borrowed
        blocks just decref back to their other owners.  Returns the
        reclaimed ids; :meth:`_release_rows` then releases the device
        row."""
        self._outstanding -= self._row_debt(i)
        reclaimed = self.pool.free(self._row_blocks[i])
        if self._row_borrowed[i]:
            reclaimed += self.pool.release(
                list(self._row_borrowed[i].values()))
        self._row_blocks[i] = []
        self._row_borrowed[i] = {}
        self._row_used[i] = 0
        self._worst[i] = 0
        self._head_reserved[i] = 0
        self._tables[i] = self.n_blocks          # sentinel
        return reclaimed

    def _release_rows(self, rows, reclaimed: list):
        """Device half of dropping ``rows`` ((n_slots,) bool): ``lens``
        -> 0 and sentinel tables.  Freed arena blocks are overwritten
        wholesale on reuse, nothing to wipe — except under the
        sanitizer, which poisons ``reclaimed`` so a stale table entry
        detonates instead of silently serving freed KV, and refreshes
        the leak gauge."""
        self.cache = self.engine.shard_cache(
            self._release(self.cache, jnp.asarray(rows)))
        if self.sanitize:
            if reclaimed:
                self.cache = self.engine.poison_blocks(
                    self.cache, reclaimed)
            self.n_leaked = len(self.leak_report())

    def _try_preempt(self, req: Request) -> bool:
        """Preemption-by-block-release: when the EDF head cannot be
        admitted, evict the active row with the LATEST deadline — only
        if strictly later than the candidate's (best-effort rows count
        as latest), so best-effort never preempts best-effort and the
        loop cannot livelock."""
        cd = float("inf") if req.deadline is None else req.deadline
        victim, vd_max = None, cd
        for i, s in enumerate(self._slots):
            if s is None or s.done:
                continue
            vd = float("inf") if s.req.deadline is None \
                else s.req.deadline
            if vd > vd_max:
                victim, vd_max = i, vd
        if victim is None:
            return False
        self._preempt_row(victim)
        return True

    def _phase(self, rid: int, name: Optional[str], *, at=None,
               **counters):
        """Close request ``rid``'s open lifecycle span at ``at`` (default
        now) with ``counters`` set, and open ``name`` (``request.*``) at
        the same instant, so a request's spans tile its life with no
        gap; ``name=None`` closes the last one."""
        t = self._life.pop(rid).set(**counters).end(at)
        if name is not None:
            self._life[rid] = spans.begin(name, start_ns=t,
                                          sched=self.sched_id, rid=rid)

    def _admit(self):
        self._order_queue()
        free = [i for i, s in enumerate(self._slots) if s is None]
        while self._queue and free:
            req = self._queue[0]
            row = free[0]
            cursor = self._admit_row(req, row)
            if cursor is None:         # pool cannot cover the request yet
                if self._try_preempt(req):
                    self._order_queue()
                    free = [i for i, s in enumerate(self._slots)
                            if s is None]
                    continue
                break                  # EDF: do not admit around the head
            self._queue.popleft()
            free.remove(row)
            self._slots[row] = _Slot(
                req=req, emitted=[],
                admitted_step=self.steps_run, cursor=cursor)
            self.n_admitted += 1
            self._phase(req.rid, "request.prefill")

    def leak_report(self) -> set:
        """Sanitizer leak accounting: allocated block ids unreachable
        from any live row's owned blocks, any borrowed table entry, or
        the prefix index.  A non-empty set means references were dropped
        without ``free``/``release`` — those blocks can never be
        reclaimed.  Valid to call any time; ``_retire`` refreshes the
        ``n_leaked`` gauge from it."""
        held: set = set()
        for ids in self._row_blocks:
            held.update(int(b) for b in ids)
        for borrowed in self._row_borrowed:
            held.update(int(b) for b in borrowed.values())
        if self.prefix_cache:
            held.update(int(b) for b in self.index.blocks_lru())
        return set(self.pool.allocated_ids()) - held

    def _retire(self):
        done_mask = np.zeros((self.n_slots,), bool)
        completions = []
        reclaimed: list = []
        for i, slot in enumerate(self._slots):
            if slot is None or not slot.done:
                continue
            done_mask[i] = True
            req = slot.req
            completions.append(Completion(
                rid=req.rid, prompt_len=len(req.prompt),
                tokens=np.asarray(slot.emitted, np.int32),
                arrival_step=req.arrival_step,
                admitted_step=slot.admitted_step,
                finished_step=self.steps_run,
                first_logits=slot.first_logits))
            self._slots[i] = None
            self.n_retired += 1
            self._phase(req.rid, None, tokens=len(slot.emitted))
            reclaimed += self._drop_row(i)
        if done_mask.any():
            self._release_rows(done_mask, reclaimed)
        return completions

    def _decode_reads(self, decode_active) -> dict:
        """The round's decode-attention counters, from host state alone:
        ``decode_steps`` the dispatch runs (0 when no row decodes);
        ``kv_live_positions``, the positions those steps attend summed
        over the decoding rows (``lens + 1``, one more each step, for
        every step the device runs, a row that finishes early included;
        at most the window where one is set); ``kv_read_positions``, the
        positions the decode attention reads.  On the gather path a step
        reads, for each of the ``n_slots`` rows, the width that
        ``layers.paged_read_positions`` gives for the step's extent (the
        decoding rows' largest ``lens + 1``): the rule the device
        branches on.  The fused table walk reads every row's whole table
        (``table_width x block_size``) each step.  ``kv_layer_slices``,
        the whole-layer arena slices those steps copy before they read:
        one per layer-step for each arena that
        ``layers.paged_reads_flat`` does not read straight off the
        stacked arena."""
        n = self.chunk_size if decode_active.any() else 0
        lens = np.array([s.lens for s, a in zip(self._slots, decode_active)
                         if a], np.int64)
        steps = np.arange(n)
        live = lens[:, None] + 1 + steps
        if self._window:
            live = np.minimum(live, self._window)
        w, bs = self.table_width, self.block_size
        cfg = self.engine.cfg
        if cfg.paged_attn_kernel == "fused" or not n:
            width = np.full(n, w * bs)
        else:
            width = L.paged_read_positions(lens.max() + 1 + steps, w, bs,
                                           window=self._window)
        arenas = [self.cache[k] for k in
                  (("c_kv", "k_rope") if cfg.mla else ("k", "v"))]
        sliced = sum(not L.paged_reads_flat(a.shape[-1],
                                            cfg.paged_attn_kernel)
                     for a in arenas)
        return dict(decode_steps=n, kv_live_positions=int(live.sum()),
                    kv_read_positions=self.n_slots * int(width.sum()),
                    kv_layer_slices=n * arenas[0].shape[0] * sliced)

    def _step(self, rnd):
        """One scheduling round's body: admit (allocation only) ->
        extend/COW/sanitize for the combined prefill+decode write spans
        -> ONE ``mixed_step`` dispatch (a prefill chunk for every
        prefilling row, the decode quantum for every decoding row —
        compiled once, for every prompt length) -> advance cursors and
        emit decode tokens -> sample first tokens for rows that
        completed their prompt -> retire."""
        with spans.span("sched.admit"):
            self._admit()
        with spans.span("sched.prepare"):
            decode_active = np.array(
                [s is not None and not s.done and s.cursor is None
                 for s in self._slots], bool)
            nv = np.zeros((self.n_slots,), np.int32)
            chunk = np.full((self.n_slots, self.chunk_size),
                            self.engine.pad_id, np.int32)
            for i, s in enumerate(self._slots):
                if s is None or s.done or s.cursor is None:
                    continue
                n = min(self.chunk_size, len(s.req.prompt) - s.cursor)
                nv[i] = n
                chunk[i, :n] = s.req.prompt[s.cursor:s.cursor + n]
            dispatch = decode_active.any() or nv.any()
            rnd.set(**self._decode_reads(decode_active))
            if dispatch:
                self._ensure_blocks()
                if self.sanitize:
                    self._sanitize_check_chunk()
                # per-round write tables: every still-borrowed entry
                # hidden behind the sentinel so shared blocks never take
                # a write (not even a byte-identical write-back from
                # pack_range)
                wt = self._tables.copy()
                for i, borrowed in enumerate(self._row_borrowed):
                    for s in borrowed:
                        wt[i, s] = self.n_blocks
        if not dispatch:
            # admissions can complete instantly only via retirement of
            # already-done slots; surface those without a dispatch
            with spans.span("sched.retire"):
                return self._retire()
        self.cache, chunk_logits, toks = self.engine.mixed_step(
            self.cache, chunk, nv, self._cur_tok, self.chunk_size,
            decode_active=decode_active, write_tables=wt)
        with spans.span("sched.device_wait"):
            toks = np.asarray(toks)
            chunk_logits = np.asarray(chunk_logits)
        self.steps_run += self.chunk_size
        self.n_chunks += 1

        completed, emitted = [], 0
        with spans.span("sched.emit"):
            for i, s in enumerate(self._slots):
                if s is None or s.done:
                    continue
                req = s.req
                if decode_active[i]:
                    n0 = len(s.emitted)
                    for t in toks[i]:
                        s.emitted.append(int(t))
                        if int(t) == req.eos_id or \
                                len(s.emitted) >= req.max_new_tokens:
                            s.done = True
                            break
                    emitted += len(s.emitted) - n0
                    self._cur_tok[i] = toks[i, -1]
                elif nv[i]:
                    s.cursor += int(nv[i])
                    self.prefill_tokens += int(nv[i])
                    if s.cursor >= len(req.prompt):
                        s.cursor = None
                        if self.prefix_cache:
                            self._register_row(req.prompt, i)
                            self._note_peaks()
                        completed.append(i)
        with spans.span("sched.first_tokens") as ft:
            # prompt complete: the first token comes from the chunk's
            # last-valid-position logits, exactly where a whole-prompt
            # prefill would have sampled it (one device round trip each)
            for i in completed:
                s = self._slots[i]
                req = s.req
                tok0, self.engine._key = sample_token(
                    jnp.asarray(chunk_logits[i:i + 1]),
                    self.engine._key, self.engine.temperature)
                tok0 = int(np.asarray(tok0)[0])
                s.first_logits = chunk_logits[i].copy()
                s.emitted.append(tok0)
                self._cur_tok[i] = tok0
                if tok0 == req.eos_id or req.max_new_tokens == 1:
                    s.done = True
                self._phase(req.rid, "request.decode", prefill_rounds=(
                    self.steps_run - s.admitted_step) // self.chunk_size)
            ft.set(syncs=len(completed))
        rnd.set(prefill_rows=int((nv > 0).sum()),
                decode_rows=int(decode_active.sum()),
                tokens_emitted=emitted + len(completed),
                first_tokens=len(completed))
        with spans.span("sched.retire"):
            return self._retire()

    def step(self):
        """One scheduling round; returns the requests completed in it.

        Order: admit queued prompts into free slots (EDF over any
        deadlines, FIFO otherwise; an admission defers until
        ``n_free + evictable - outstanding`` covers its worst-case
        block demand, preempting a strictly-later-deadline row if that
        unblocks the head) -> extend live dense rows' tables / COW
        window-lane ring slots about to recycle a shared block -> ONE
        ``mixed_step`` dispatch (shapes never change: every prefilling
        row's prompt chunk, then the decode quantum) -> retire finished
        rows (decref their blocks; prefix-registered blocks stay
        resident under the index's reference).  Invariants pinned by tests: greedy token
        streams identical to isolated generation and to the
        non-sharing paged path; writes reach a block only while its
        refcount is 1; reservation never lets extension or COW find
        the pool empty.

        Every round is a ``sched.round`` span (``runtime/spans.py``)
        whose children are its phases (``sched.admit``,
        ``sched.prepare``, ``engine.dispatch``, ``sched.device_wait``,
        ``sched.emit``, ``sched.first_tokens``, ``sched.retire``) and
        whose counters say what it did; ``compiled`` is the engine's
        new compiles in the round, non-zero only on a round that
        compiled.  Every round also counts its decode attention's
        work (``decode_steps``, ``kv_live_positions``,
        ``kv_read_positions``, ``kv_layer_slices``;
        :meth:`_decode_reads`).  ``stats`` reads
        the round spans' p50/p99."""
        with spans.span("sched.round", sched=self.sched_id,
                        round=self._round) as rnd:
            before = (self.n_admitted, self.n_retired, self.prefill_tokens,
                      self.engine.n_compiles)
            rnd.set(round=self._round, prefill_rows=0, decode_rows=0,
                    tokens_emitted=0, first_tokens=0)
            done = self._step(rnd)
            rnd.set(admitted=self.n_admitted - before[0],
                    retired=self.n_retired - before[1],
                    prefill_tokens=self.prefill_tokens - before[2],
                    queue_depth=len(self._queue),
                    compiled=self.engine.n_compiles - before[3])
        self._round += 1
        return done

    def run(self, max_rounds: Optional[int] = None):
        """Drain queue + slots; returns ``{rid: Completion}``."""
        out = {}
        rounds = 0
        while self.has_work:
            if max_rounds is not None and rounds >= max_rounds:
                raise RuntimeError(
                    f"scheduler did not drain in {max_rounds} rounds "
                    f"({len(self._queue)} queued, {self.n_active} active)")
            for c in self.step():
                out[c.rid] = c
            rounds += 1
        return out
