"""Posit KV-cache quantization utilities (serving memory/bandwidth).

The models quantize/dequantize inline (see ``models/*.py``); these helpers
quantize an *existing* cache tree (e.g. after prefill in f32) and report
compression ratios for the benchmarks.

Cache *maintenance* ops (``scale_cache``, ``merge_caches``) stay entirely
in the posit domain via the fused Pallas elementwise kernels
(``repro.kernels.ops``): one decode->arith->encode pass per element
instead of the dequantize -> f32 op -> requantize round-trip, so a cache
rescale (attention-sink discounting, temperature folding) or a
speculative-decoding cache merge rounds once, not twice.

This module also owns the serving cache MEMORY model: the paged
layout's ``BlockPool`` free list, the prefix index, and block-table
surgery (``paged_release_rows``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax import tree_util

from repro.core.convert import f32_to_posit, posit_to_f32
from repro.core.tracing import is_tracer as _is_tracer
from repro.kernels import ops as kops
from .gradient import pcfg_of, scalar_pattern


# ---------------------------------------------------------------------------
# Explicit cache-leaf schema (pattern vs metadata tagging)
#
# Caches are plain dict pytrees, so the leaf NAME is the tag: every cache
# content leaf (K/V, latents, recurrent state — the things a posit codec
# may have quantized to unsigned patterns) is registered in
# ``CONTENT_LEAVES``; bookkeeping (frontiers, per-row lengths, paged block
# tables) in ``META_LEAVES``.  The old heuristic sniffed ``unsignedinteger``
# dtypes, which misclassifies any unsigned bookkeeping leaf (a uint block
# table would have been "scaled" as posit patterns) and cannot distinguish
# an f32 cache's content from metadata.  Unknown unsigned leaves now raise
# instead of guessing.
# ---------------------------------------------------------------------------

# Time-axis content (the paged arenas' leaves).
_TIME_LEAVES = frozenset(
    {"k", "v", "c_kv", "k_rope", "k_swa", "v_swa", "k_glb", "v_glb"})
# Per-row state without a time axis.
_ROW_LEAVES = frozenset({"ssm"})
# All content: time leaves + row state + whisper cross-attention KV +
# rwkv recurrent state.
CONTENT_LEAVES = _TIME_LEAVES | _ROW_LEAVES | frozenset(
    {"ck", "cv", "wkv", "tm_x", "cm_x"})
META_LEAVES = frozenset(
    {"len", "lens", "max_len", "length", "block_tables"})


def _leaf_key(path):
    for entry in reversed(path):
        if isinstance(entry, tree_util.DictKey):
            return str(entry.key)
        if isinstance(entry, tree_util.GetAttrKey):
            return str(entry.name)
    return None


def _leaf_is_patterns(path, x) -> bool:
    key = _leaf_key(path)
    if key is None:                 # bare array / unkeyed tree: dtype only
        return jnp.issubdtype(x.dtype, jnp.unsignedinteger)
    if key in CONTENT_LEAVES:
        return jnp.issubdtype(x.dtype, jnp.unsignedinteger)
    if key in META_LEAVES:
        return False
    if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        raise ValueError(
            f"unknown unsigned cache leaf {key!r}: register it in "
            "kvcache.CONTENT_LEAVES (posit patterns) or "
            "kvcache.META_LEAVES (bookkeeping); refusing to guess from "
            "the dtype")
    return False


def _leaf_is_content(path, x) -> bool:
    key = _leaf_key(path)
    if key is None:
        return (jnp.issubdtype(x.dtype, jnp.unsignedinteger)
                or jnp.issubdtype(x.dtype, jnp.floating))
    return key in CONTENT_LEAVES


def quantize_cache(cache, name: str):
    cfg = pcfg_of(name)

    def one(path, x):
        if _leaf_is_content(path, x) and \
                jnp.issubdtype(x.dtype, jnp.floating):
            return f32_to_posit(x.astype(jnp.float32), cfg)
        key = _leaf_key(path)
        if key is not None and key not in META_LEAVES and \
                key not in CONTENT_LEAVES and \
                jnp.issubdtype(x.dtype, jnp.floating):
            raise ValueError(
                f"unknown float cache leaf {key!r}: register it in "
                "kvcache.CONTENT_LEAVES (quantizable content) or "
                "kvcache.META_LEAVES (bookkeeping); refusing to "
                "silently skip it")
        return x                                   # lengths / ints

    return tree_util.tree_map_with_path(one, cache)


def dequantize_cache(cache, name: str):
    cfg = pcfg_of(name)

    def one(path, x):
        if _leaf_is_patterns(path, x):
            return posit_to_f32(x, cfg)
        return x

    return tree_util.tree_map_with_path(one, cache)


def cache_bytes(cache) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


def _shard_bytes(x) -> int:
    """Bytes of ``x`` resident on one device: the per-shard shape when
    the leaf carries a (Named)Sharding, the full size otherwise (plain
    numpy leaves, abstract shapes)."""
    sharding = getattr(x, "sharding", None)
    if sharding is None or not hasattr(sharding, "shard_shape"):
        return x.size * x.dtype.itemsize
    shape = sharding.shard_shape(x.shape)
    n = 1
    for s in shape:
        n *= int(s)
    return n * x.dtype.itemsize


def cache_report(cache, pool=None) -> dict:
    """Actual vs f32-equivalent bytes and the compression ratio.

    Content leaves (posit patterns or reduced-precision floats, per the
    explicit ``CONTENT_LEAVES`` schema) count 4 bytes/element in the f32
    baseline; bookkeeping (``len``/``lens``/``max_len``/``block_tables``)
    counts as-is.  Shape-agnostic, so it reports ring-buffer
    (window-sized) and paged (block-arena) caches the same way as linear
    ones — the ratio compares storage *dtypes*, not layouts, while
    ``bytes`` reflects the layout's actual footprint (a paged arena
    sized below ``slots x max_len`` reports correspondingly fewer
    bytes).

    ``pool`` (a :class:`BlockPool`) extends the report for paged caches
    with the PHYSICAL vs LOGICAL block split prefix sharing creates:
    ``physical_blocks`` are resident arena blocks, ``logical_blocks``
    sum the references to them (what a non-sharing pool would hold),
    and the peaks record the trace high-water marks.  With no sharing
    the two columns are equal; their gap is the deduplication win.

    ``per_device_bytes`` is the cache's footprint on ONE device, read
    off each leaf's actual sharding (``shard_shape``): equal to
    ``bytes`` on a single device or a replicated placement, and
    ``arena/model_parallel`` + replicated metadata when the arena is
    head-sharded over a mesh — the number the sharded serving
    benchmark asserts drops ~linearly with the model-parallel degree.
    """
    leaves = tree_util.tree_leaves_with_path(cache)
    actual = sum(x.size * x.dtype.itemsize for _, x in leaves)
    f32 = sum(
        x.size * 4 if _leaf_is_content(p, x) else x.size * x.dtype.itemsize
        for p, x in leaves)
    out = {"bytes": actual, "f32_bytes": f32,
           "ratio": f32 / max(actual, 1),
           "per_device_bytes": sum(_shard_bytes(x) for _, x in leaves)}
    if pool is not None:
        out.update(
            physical_blocks=pool.in_use,
            logical_blocks=pool.logical_in_use,
            peak_physical_blocks=pool.peak_in_use,
            peak_logical_blocks=pool.peak_logical)
    return out


def is_paged(cache) -> bool:
    """True for block-table (paged) caches: their batch rows address the
    shared block arena through per-row tables (see the paged section)."""
    return isinstance(cache, dict) and "block_tables" in cache


# ---------------------------------------------------------------------------
# Posit-domain cache maintenance (fused elementwise kernels)
# ---------------------------------------------------------------------------

def scale_cache(cache, factor: float, name: str,
                interpret: Optional[bool] = None):
    """Multiply every quantized leaf by ``factor`` in the posit domain.

    Pattern leaves are identified by the explicit ``CONTENT_LEAVES``
    schema (not dtype sniffing); metadata (lengths, positions, block
    tables) passes through untouched.
    """
    cfg = pcfg_of(name)
    s = scalar_pattern(factor, cfg)

    def one(path, x):
        if _leaf_is_patterns(path, x):
            return kops.vmul(x, s, cfg, interpret=interpret)
        return x

    return tree_util.tree_map_with_path(one, cache)


def merge_caches(cache_a, cache_b, name: str, weight_a: float = 0.5,
                 interpret: Optional[bool] = None):
    """Blend two quantized caches: ``wa * a + (1 - wa) * b``, fused.

    Three posit-domain ops (two vmul, one vadd) — each exactly rounded —
    versus two full dequantize passes, three f32 ops, and a requantize.

    Non-pattern leaves (lengths, positions) must agree between the two
    caches — blending the K/V contents of caches with different metadata
    would silently produce an inconsistent cache, so that is an error.
    The guard is trace-safe: shape/dtype mismatches raise even under
    ``jax.jit`` (they are static), while the value-equality check runs
    only on concrete (non-tracer) leaves — a jitted merge trusts the
    caller's metadata values, as a host-side guard cannot inspect
    traced data without aborting the trace.
    """
    cfg = pcfg_of(name)
    wa = scalar_pattern(weight_a, cfg)
    wb = scalar_pattern(1.0 - float(weight_a), cfg)

    def one(path, a, b):
        if _leaf_is_patterns(path, a) and _leaf_is_patterns(path, b):
            return kops.vadd(kops.vmul(a, wa, cfg, interpret=interpret),
                             kops.vmul(b, wb, cfg, interpret=interpret),
                             cfg, interpret=interpret)
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(
                "merge_caches: non-pattern (metadata) leaves differ "
                f"between caches: {a.shape}/{a.dtype} vs "
                f"{b.shape}/{b.dtype}; refusing to blend K/V contents "
                "of inconsistent caches")
        if (not _is_tracer(a) and not _is_tracer(b)
                and not bool(jnp.all(a == b))):
            raise ValueError(
                "merge_caches: non-pattern (metadata) leaves differ "
                f"between caches (shape {a.shape}); refusing to blend "
                "K/V contents of inconsistent caches")
        return a

    return tree_util.tree_map_with_path(one, cache_a, cache_b)


# ---------------------------------------------------------------------------
# Paged KV cache: a BlockPool free-list over a global arena of fixed-size
# posit-pattern blocks, plus per-sequence block tables.
#
# Layout (see ``models/transformer.py`` for the model-side lanes):
#   * arena content leaves are (L, n_blocks, block_size, ...) — one global
#     pool of blocks shared by every batch row;
#   * ``block_tables`` is (B, W) int32: row b's logical block i lives in
#     physical arena block ``block_tables[b, i]``; unassigned entries hold
#     the OUT-OF-RANGE sentinel ``n_blocks`` so a write through them is
#     DROPPED by the scatter (the paged re-expression of the engine's
#     never-clamp guarantee) and a gather through them clamps into masked
#     garbage;
#   * addressing is ROW-LOCAL: row b's token p occupies logical block
#     ``p // block_size`` at offset ``p % block_size``.  There is no
#     shared padded frontier (no ``len`` leaf): a row grows into freshly
#     allocated blocks, and retirement frees them back to the pool.
#
# The ``BlockPool`` itself is HOST state (a free list): block ids only
# cross to the device inside ``block_tables``.
# ---------------------------------------------------------------------------


class BlockSanitizerError(ValueError):
    """Arena-sanitizer violation: double free, use-after-free, a write
    into a shared (refcount > 1) block that skipped copy-on-write, or a
    wild block id.  Subclasses ``ValueError`` so callers guarding the
    plain allocator errors keep working."""


class BlockPool:
    """Host-side refcounted allocator over ``n_blocks`` arena block ids.

    Contract (pinned by ``tests/test_paged.py`` and
    ``tests/test_prefix.py``):

    * ``alloc(n)`` hands out ``n`` distinct PHYSICALLY free blocks, each
      with refcount 1.  A block is never handed out twice while any
      reference to it is live.
    * ``share(ids)`` increments refcounts — how a request (or the
      scheduler's :class:`PrefixIndex`) borrows blocks another owner
      packed.  Sharing never moves or copies data; it only pins the
      block against physical reclaim.
    * ``free(ids)`` / ``release(ids)`` (aliases) DECREMENT refcounts;
      the block returns to the free list only when its refcount reaches
      zero.  Dropping a reference that is not held raises (the double
      free guard).
    * ``in_use`` counts PHYSICAL resident blocks;
      ``logical_in_use`` counts references (what a non-sharing pool
      would have resident).  ``logical_in_use - in_use`` is therefore
      the blocks deduplication is currently saving.
    * ``peak_in_use`` / ``peak_logical`` are the corresponding
      high-water marks (capacity planning / the benchmark's
      physical-vs-logical report).

    Sanitizer mode (``BlockPool(n, sanitize=True)``, opt-in): misuse of
    freed ids raises :class:`BlockSanitizerError` with a use-after-free
    vs double-free diagnosis, and the ``check_write``/``check_read``
    gates let the scheduler validate every block a device scatter/gather
    is about to touch — including the COW invariant (no write into a
    refcount > 1 block).  The engine pairs this with device-side
    poisoning of reclaimed blocks (``layers.paged_poison_blocks``) so a
    stale table entry that slips past the host checks detonates the
    logits instead of silently serving freed KV.
    """

    def __init__(self, n_blocks: int, *, sanitize: bool = False):
        if n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
        self.n_blocks = int(n_blocks)
        self._free = list(range(self.n_blocks - 1, -1, -1))  # pop() -> asc
        self._ref: dict = {}            # block id -> refcount (>= 1)
        self.peak_in_use = 0
        self.peak_logical = 0
        # Sanitizer mode (opt-in, see class docstring): track which ids
        # have been freed and not since reallocated so misuse reports can
        # tell use-after-free from a wild/foreign id, and upgrade the
        # guards to ``check_write``/``check_read`` entry points callers
        # (the scheduler) invoke before touching the device arena.
        self.sanitize = bool(sanitize)
        self._freed: set = set()        # freed and not yet reallocated
        self.n_sanitizer_checks = 0

    @property
    def n_free(self) -> int:
        """Physically free blocks (refcount zero)."""
        return len(self._free)

    @property
    def in_use(self) -> int:
        """Physically resident blocks (refcount >= 1)."""
        return len(self._ref)

    @property
    def logical_in_use(self) -> int:
        """Sum of refcounts: the blocks a non-sharing pool would hold."""
        return sum(self._ref.values())

    def refcount(self, block_id: int) -> int:
        """Live references to ``block_id`` (0 = physically free)."""
        return self._ref.get(int(block_id), 0)

    def _note_peaks(self):
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        self.peak_logical = max(self.peak_logical, self.logical_in_use)

    def alloc(self, n: int) -> list:
        """Take ``n`` physically free blocks, refcount 1 each."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            raise MemoryError(
                f"BlockPool exhausted: {n} blocks requested, "
                f"{len(self._free)} free of {self.n_blocks}")
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self._ref[i] = 1
            self._freed.discard(i)
        self._note_peaks()
        return ids

    def share(self, ids) -> None:
        """Increment refcounts: borrow already-resident blocks."""
        ids = [int(i) for i in ids]
        for i in ids:
            if i not in self._ref:
                if self.sanitize and i in self._freed:
                    raise BlockSanitizerError(
                        f"use-after-free: BlockPool.share of block {i}, "
                        "which is not allocated (freed earlier and not "
                        "reallocated)")
                raise ValueError(
                    f"BlockPool.share: block {i} is not allocated; only "
                    "resident blocks can be shared")
        for i in ids:
            self._ref[i] += 1
        self._note_peaks()

    def free(self, ids) -> list:
        """Drop one reference per id; physical reclaim at refcount zero.

        Returns the ids physically reclaimed by THIS call (refcount hit
        zero) — the sanitizer poisons exactly those arena blocks.
        """
        ids = [int(i) for i in ids]
        for i in ids:
            if i not in self._ref:
                if self.sanitize and i in self._freed:
                    raise BlockSanitizerError(
                        f"double free: block {i} is not allocated "
                        "(already freed and not reallocated)")
                raise ValueError(
                    f"BlockPool.free: block {i} is not allocated "
                    "(double free or foreign id)")
        reclaimed = []
        for i in ids:
            self._ref[i] -= 1
            if self._ref[i] == 0:
                del self._ref[i]
                self._free.append(i)
                self._freed.add(i)
                reclaimed.append(i)
        return reclaimed

    # ``release`` is the sharing-side name for the same decref.
    release = free

    def allocated_ids(self) -> list:
        """Sorted ids of physically resident blocks (refcount >= 1)."""
        return sorted(self._ref)

    def check_write(self, ids) -> None:
        """Sanitizer gate for an imminent arena write into ``ids``.

        Raises :class:`BlockSanitizerError` on a write into a block that
        is not allocated (use-after-free / wild write) or whose refcount
        is > 1 — a shared block being written without copy-on-write,
        which would silently corrupt every other owner's KV.
        """
        self.n_sanitizer_checks += 1
        for i in (int(i) for i in ids):
            rc = self._ref.get(i)
            if rc is None:
                kind = ("use-after-free" if i in self._freed
                        else "unallocated (wild)")
                raise BlockSanitizerError(
                    f"{kind} write: block {i} is not allocated")
            if rc > 1:
                raise BlockSanitizerError(
                    f"COW violation: write into block {i} with refcount "
                    f"{rc} — shared blocks must be copied "
                    "(copy-on-write) before the first write")

    def check_read(self, ids) -> None:
        """Sanitizer gate for reads: every id must be resident."""
        self.n_sanitizer_checks += 1
        for i in (int(i) for i in ids):
            if i not in self._ref:
                kind = ("use-after-free" if i in self._freed
                        else "unallocated (wild)")
                raise BlockSanitizerError(
                    f"{kind} read: block {i} is not allocated")


def prefix_block_hashes(tokens, block_size: int) -> list:
    """Rolling content hash of each FULL block of a token sequence.

    ``out[i]`` identifies the (i+1)-block-long prefix ``tokens[:(i+1)*bs]``
    — each hash chains the previous one, so two sequences share
    ``out[i]`` iff they agree on every token up to and including block
    ``i``.  Partial trailing blocks get no hash: only blocks whose
    content can never grow are content-addressable (a half-filled block
    would change identity on the next decode write).
    """
    bs = int(block_size)
    toks = [int(t) for t in tokens]
    out = []
    h = None
    for i in range(len(toks) // bs):
        h = hash((h,) + tuple(toks[i * bs:(i + 1) * bs]))
        out.append(h)
    return out


class PrefixIndex:
    """Content-addressed map: rolling block hash -> resident arena block.

    The scheduler registers every fully-written prompt block here and
    holds ONE pool reference per registered block, so cached prefixes
    stay resident after their owner retires.  Entries are kept in LRU
    order; a block whose only remaining reference is the index's
    (``pool.refcount == 1``) is *evictable* — the scheduler reclaims
    those, oldest first, when admission needs physical blocks.
    First-writer-wins: registering a hash that is already mapped is a
    no-op (the resident copy keeps serving matches).
    """

    def __init__(self):
        from collections import OrderedDict
        self._by_hash: "OrderedDict" = OrderedDict()   # hash -> block id
        self._by_block: dict = {}                      # block id -> hash

    def __len__(self) -> int:
        return len(self._by_hash)

    def get(self, h):
        """Resident block id for hash ``h`` (None = miss); bumps LRU."""
        if h in self._by_hash:
            self._by_hash.move_to_end(h)
            return self._by_hash[h]
        return None

    def put(self, h, block_id: int) -> bool:
        """Register ``block_id`` under ``h``; False if already mapped."""
        if h in self._by_hash:
            return False
        block_id = int(block_id)
        if block_id in self._by_block:
            raise ValueError(
                f"PrefixIndex.put: block {block_id} already registered "
                f"under another hash")
        self._by_hash[h] = block_id
        self._by_block[block_id] = h
        return True

    def pop_block(self, block_id: int):
        """Drop the entry for ``block_id`` (eviction / physical free)."""
        h = self._by_block.pop(int(block_id), None)
        if h is not None:
            del self._by_hash[h]
        return h

    def blocks_lru(self) -> list:
        """Registered block ids, least-recently-matched first."""
        return list(self._by_hash.values())


def paged_release_rows(cache, rows):
    """Retire paged batch rows: ``lens -> 0`` and their block-table rows
    reset to the sentinel, so stale entries can neither be written (the
    scatter drops sentinel targets) nor keep referencing blocks the
    caller is about to hand back to the pool.  The arena content itself
    is NOT wiped: freed blocks are overwritten wholesale on their next
    allocation and masked by ``lens`` until then.  The caller owns the
    host-side ``BlockPool.free``.
    """
    if not is_paged(cache):
        raise ValueError("paged_release_rows: cache is not paged")
    rows = jnp.asarray(rows, bool)
    tables = cache["block_tables"]
    sentinel = jnp.full_like(tables, _paged_sentinel(cache))
    return dict(
        cache,
        block_tables=jnp.where(rows[:, None], sentinel, tables),
        lens=jnp.where(rows, 0, jnp.asarray(cache["lens"], jnp.int32)))


def _paged_sentinel(cache) -> int:
    """The invalid block id (== n_blocks, from any arena leaf's shape)."""
    for key in _TIME_LEAVES:
        if key in cache:
            return int(cache[key].shape[1])
    raise ValueError("paged cache has no arena content leaves")
