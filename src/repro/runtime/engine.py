"""Serving engine: preallocated posit KV caches + one-jit scan decode.

The engine is the correct-by-construction replacement for the old
prefill-then-Python-loop serving path, which was numerically wrong: the
prefill cache had no decode headroom, so every ``decode_step`` past the
first clamp-overwrote the final KV slot (``dynamic_update_slice_in_dim``
clamps out-of-range starts).  The engine:

* **preallocates** every cache to ``max_len`` up front (posit-compressed
  when ``cfg.kv_posit`` is set) and statically refuses requests that
  would not fit — decode can never run past capacity;
* runs **ring buffers** for sliding-window caches (capacity = window,
  writes at ``pos % window``, rotation-aware masks in
  ``decode_attention``);
* decodes with a single ``lax.scan`` — one compiled call per
  ``max_new_tokens``, no per-token Python dispatch;
* **batches ragged prompts** (transformer family): prompts are
  left-padded to a common length, each row carries its own length, RoPE
  positions and attention masks are per-row — the seed of continuous
  batching;
* samples greedily or with temperature, batched, from one PRNG stream;
* optionally runs the **paged** cache layout (``paged=True``,
  transformer family): a block arena + per-row block tables replaces
  the dense ``batch x max_len`` preallocation, rows allocate blocks
  from a host-side ``kvcache.BlockPool`` as they grow, and the token
  streams are byte-identical to the dense layout's;
* serves **chunked prefill** through :meth:`Engine.mixed_step`: fixed
  ``C``-token prompt chunks and masked decode steps share ONE compiled
  dispatch shape keyed ``("mixed", C, n_steps)``, so prompt length
  never jit-specializes anything (``n_compiles`` stays flat).

Usage::

    from repro.runtime.engine import Engine
    eng = Engine(cfg, params, max_len=256, temperature=0.0, seed=0)
    res = eng.generate([[5, 3, 9], [7, 2, 4, 4, 1]], max_new_tokens=32)
    res.tokens          # (2, 32) int32
    res.prompt_lens     # [3, 5]
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.compress import kvcache as kvc
from repro.models import get_family
from repro.models.config import ModelConfig
from repro.runtime import sharding as shd
from repro.runtime import spans


def sample_token(logits, key, temperature: float):
    """(B,V) f32 logits -> ((B,) int32 token, advanced key).

    ``temperature`` is static: 0 is greedy argmax (consumes no
    randomness), > 0 is softmax sampling at that temperature.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
    key, sub = jax.random.split(key)
    tok = jax.random.categorical(sub, logits / temperature, axis=-1)
    return tok.astype(jnp.int32), key


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray        # (B, max_new_tokens) int32
    prompt_lens: np.ndarray   # (B,) int32 per-slot prompt lengths
    prefill_logits: np.ndarray  # (B, V) f32 logits after the prompt
    cache: Any                # final engine-shaped cache pytree


class Engine:
    """Batched serving engine over the four-family model protocol."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int,
                 temperature: float = 0.0, seed: int = 0,
                 pad_id: int = 0, paged: bool = False,
                 block_size: int = 16, n_blocks: int = 0,
                 sanitize: bool = False, decode_kernel: str = None,
                 mesh=None):
        """``paged=True`` swaps the dense preallocated cache for the
        block-table layout (transformer family only): prefill allocates
        arena blocks per row from a host-side ``BlockPool`` free list
        instead of reserving ``batch x max_len`` slots up front.
        ``n_blocks`` sizes the shared arena (0 = worst case, one full
        table per row — no memory win, but never out of blocks).
        ``sanitize=True`` arms the arena sanitizer: pools are created
        with ``BlockPool(sanitize=True)`` (double-free/use-after-free/
        COW-skip detection) and reclaimed blocks are poisoned on device
        via :meth:`poison_blocks` so stale table entries detonate.
        ``decode_kernel`` selects the paged decode-attention path:
        ``'gather'`` (jnp reference) or ``'fused'`` (the Pallas
        block-table-walk kernel, ``kernels/posit_paged_attn.py``);
        it threads through ``cfg.paged_attn_kernel`` so every jitted
        decode program closes over the choice.
        ``mesh`` (a ``jax.sharding.Mesh`` with a 'model' axis, e.g. from
        ``launch.mesh.make_host_mesh``) serves tensor-parallel: the
        weights are placed by the ``runtime/sharding.py`` rule table,
        paged pool caches get head-sharded arenas via
        :meth:`shard_cache`, and every dispatch runs inside the mesh
        context so the model-side sharding constraints resolve.  Token
        streams are identical to the mesh-less engine's."""
        if decode_kernel is not None:
            if decode_kernel not in ("gather", "fused"):
                raise ValueError(
                    f"decode_kernel must be 'gather' or 'fused', got "
                    f"{decode_kernel!r}")
            if not paged:
                raise ValueError(
                    "decode_kernel selects the PAGED decode attention "
                    "path; construct the engine with paged=True")
            cfg = dataclasses.replace(cfg, paged_attn_kernel=decode_kernel)
        self.cfg = cfg
        self.params = params
        self.fam = get_family(cfg)
        self.max_len = int(max_len)
        self.temperature = float(temperature)
        self.pad_id = int(pad_id)
        self.paged = bool(paged)
        self.block_size = int(block_size)
        self.n_blocks = int(n_blocks)
        self.sanitize = bool(sanitize)
        if self.paged:
            if cfg.family != "transformer":
                raise ValueError(
                    "paged KV caches need the transformer family's "
                    f"per-row decode positions (got {cfg.family!r})")
            if self.block_size < 1:
                raise ValueError(
                    f"block_size must be >= 1, got {self.block_size}")
            from repro.models import layers as L
            from repro.models import transformer as T
            self.table_width = T.paged_table_width(
                cfg, self.block_size, self.max_len)
            self.window_lane = L.paged_is_window_lane(
                T._paged_window(cfg), self.block_size, self.table_width)
        self.mesh = mesh
        if mesh is not None:
            # one-time placement: TP rules from the sharding table;
            # every later dispatch sees committed sharded weights and
            # compiles SPMD against them
            self.params = jax.device_put(
                params, shd.param_shardings(params, mesh))
        self.pool = None               # BlockPool of the last paged prefill
        self._key = jax.random.PRNGKey(seed)
        self._prefill_jit = {}
        self._decode_jit = {}

    def shard_cache(self, cache):
        """Place a paged pool cache on the engine mesh: dense arena
        leaves head-sharded over 'model', MLA latents and metadata
        replicated (``sharding.paged_cache_specs``).  Identity without
        a mesh; a no-op for leaves already canonically placed — also
        used between dispatches to keep the cache's shardings stable so
        the serving loop never recompiles on a sharding change."""
        if self.mesh is None:
            return cache
        return jax.device_put(
            cache, shd.paged_cache_shardings(cache, self.mesh, self.cfg))

    def _dispatch(self, fn, *args):
        """Invoke a jitted callable inside the engine mesh context when
        one is set, so ``PartitionSpec`` sharding constraints in the
        model code resolve (they no-op without a mesh)."""
        if self.mesh is None:
            return fn(*args)
        with jax.set_mesh(self.mesh):
            return fn(*args)

    @property
    def n_compiles(self) -> int:
        """Distinct lowered programs this engine has compiled: the sum
        of every cached jit callable's trace-cache size, so per-shape
        retraces INSIDE one callable count too (``prefill`` retraces per
        padded prompt length without ever missing the engine's own jit
        cache).  The scheduler surfaces it in ``Scheduler.stats``; its
        one ``mixed_step`` program keeps it flat after warmup no matter
        how ragged the admitted prompt lengths are
        (tests/test_scheduler.py)."""
        n = 0
        for store in (self._prefill_jit, self._decode_jit):
            for fn in store.values():
                sz = getattr(fn, "_cache_size", None)
                n += sz() if callable(sz) else 1
        return n

    def _get_jit(self, store: dict, key, build):
        """Jit-cache lookup: a miss builds one new jitted callable
        (whose compilations ``n_compiles`` then tracks)."""
        if key not in store:
            store[key] = build()
        return store[key]

    # ------------------------------------------------------------------
    # prompt packing
    # ------------------------------------------------------------------

    def pack_prompts(self, prompts):
        """list-of-token-lists (or a 2-D array) -> left-padded (B,S)
        int32 tokens + (B,) int32 lens."""
        arr = np.asarray(prompts, dtype=object) \
            if not isinstance(prompts, (np.ndarray, jnp.ndarray)) else prompts
        if isinstance(arr, (np.ndarray, jnp.ndarray)) and arr.ndim == 2 \
                and arr.dtype != object:
            tokens = np.asarray(arr, np.int32)
            lens = np.full((tokens.shape[0],), tokens.shape[1], np.int32)
            return tokens, lens
        lens = np.asarray([len(p) for p in prompts], np.int32)
        s = int(lens.max())
        tokens = np.full((len(prompts), s), self.pad_id, np.int32)
        for i, p in enumerate(prompts):                   # left-pad
            tokens[i, s - len(p):] = np.asarray(p, np.int32)
        return tokens, lens

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------

    def _prefill_fn(self, ragged: bool, kw_names: tuple,
                    n_blocks: int = 0):
        cfg, fam, ml, bs = self.cfg, self.fam, self.max_len, \
            self.block_size

        def prefill_batch(params, tokens, lens, *kw_vals):
            kw = dict(zip(kw_names, kw_vals))    # tables ride past the zip
            if n_blocks:
                kw.update(block_tables=kw_vals[-1], block_size=bs,
                          n_blocks=n_blocks)
            if ragged:
                return fam.prefill(params, tokens, cfg, max_len=ml,
                                   prompt_lens=lens, **kw)
            return fam.prefill(params, tokens, cfg, max_len=ml, **kw)

        return jax.jit(prefill_batch)

    def _row_blocks_needed(self, prompt_len: int, reserve: int) -> int:
        """Blocks covering a row's prompt plus ``reserve`` decode
        writes (window rows hold the full bounded ring)."""
        if self.window_lane:
            return self.table_width
        need = min(prompt_len + reserve, self.max_len)
        return -(-need // self.block_size)

    def _alloc_tables(self, lens, reserve: int, n_blocks: int,
                      pool=None):
        """Host-side block allocation for a prompt batch: returns the
        (B, W) int32 table (sentinel = n_blocks in unassigned entries)
        and the pool it drew from."""
        pool = pool or kvc.BlockPool(n_blocks, sanitize=self.sanitize)
        tables = np.full((len(lens), self.table_width), n_blocks,
                         np.int32)
        for row, pl in enumerate(lens):
            need = self._row_blocks_needed(int(pl), reserve)
            tables[row, :need] = pool.alloc(need)
        return tables, pool

    def prefill(self, prompts, *, frames=None, visual=None,
                reserve_tokens: int = 0):
        """Run the (possibly ragged) prompt batch; returns
        (cache, last-position logits (B,V), lens (B,)).

        On a paged engine, each row gets arena blocks covering its
        prompt plus ``reserve_tokens`` decode writes (``generate``
        reserves its whole budget up front so the one-scan decode never
        needs new blocks).
        """
        tokens, lens = self.pack_prompts(prompts)
        b, s = tokens.shape
        if s > self.max_len:
            raise ValueError(
                f"padded prompt length {s} exceeds engine max_len "
                f"{self.max_len}")
        ragged = bool((lens != lens[0]).any())
        if ragged and self.cfg.family != "transformer":
            raise ValueError(
                "ragged prompt batches are only supported for the "
                f"transformer family (got family={self.cfg.family!r}); "
                "pad or bucket the prompts")
        if ragged and visual is not None:
            raise ValueError(
                "ragged prompt batches cannot carry a visual prefix: "
                "patch embeddings are prepended at the sequence front, "
                "which is where left-padding lives; pad the prompts to a "
                "common length instead")
        kw = {k: v for k, v in (("frames", frames), ("visual", visual))
              if v is not None}
        args = [kw[k] for k in sorted(kw)]
        nb = 0
        if self.paged:
            nb = self.n_blocks or b * self.table_width
            tables, self.pool = self._alloc_tables(
                lens, int(reserve_tokens), nb)
            args.append(jnp.asarray(tables))
        key = (ragged, tuple(sorted(kw)), nb)
        fn = self._get_jit(self._prefill_jit, key,
                           lambda: self._prefill_fn(
                               ragged, tuple(sorted(kw)), n_blocks=nb))
        cache, logits = self._dispatch(
            fn, self.params, jnp.asarray(tokens), jnp.asarray(lens), *args)
        if self.paged:
            cache = self.shard_cache(cache)
        return cache, logits, lens

    # ------------------------------------------------------------------
    # mixed dispatch: prefill chunks + decode steps, one compiled shape
    # ------------------------------------------------------------------

    def _mixed_fn(self, n_steps: int):
        """One compiled program = one prefill chunk over every row
        (rows with ``n_valid == 0`` are exact no-ops) followed by
        ``n_steps`` masked decode steps.  The shapes depend only on
        (batch, chunk width, n_steps) — never on any prompt length — so
        the scheduler compiles this ONCE and serves every request with
        it.

        Its module is ``jit_run``, a name no other engine program takes,
        so a profile finds the serving round by it; ``jax.named_scope``
        names its phases (``prefill_chunk``, ``sample``; the model code
        adds ``decode_attn``, ``kv_decode``, ``mlp``, ``lm_head``) in the
        ops' metadata, which leaves the compiled program unchanged."""
        from repro.models import transformer as T
        cfg, fam, temp = self.cfg, self.fam, self.temperature
        vw = -(-self.max_len // self.block_size)

        def run(params, cache, chunk_tokens, n_valid, tok, key,
                decode_active, write_tables):
            with jax.named_scope("prefill_chunk"):
                cache, chunk_logits = T.prefill_chunk(
                    params, cache, chunk_tokens, cfg, n_valid,
                    virtual_width=vw, write_tables=write_tables)

            def step(carry, _):
                cache, tok, key = carry
                logits, cache = fam.decode_step(params, cache, tok, cfg,
                                                active=decode_active)
                with jax.named_scope("sample"):
                    nxt, key = sample_token(logits, key, temp)
                return (cache, nxt, key), nxt

            (cache, _, key), toks = lax.scan(
                step, (cache, tok, key), length=n_steps)
            return cache, chunk_logits, toks.T, key

        return jax.jit(run)

    def mixed_step(self, cache, chunk_tokens, n_valid, tokens,
                   n_steps: int, *, decode_active=None,
                   write_tables=None):
        """Advance prefilling AND decoding rows in one compiled dispatch
        (paged transformer engines only).

        Phase 1 runs ``T.prefill_chunk``: row ``b`` appends
        ``chunk_tokens[b, :n_valid[b]]`` at positions ``lens[b]...`` of
        its paged cache (``n_valid[b] == 0`` rows — decoding or idle —
        are untouched).  Phase 2 runs ``n_steps`` masked decode steps
        for rows with ``decode_active`` set, fed by ``tokens`` (the last
        sampled token per row; garbage for non-decoding rows, whose
        writes are dropped).  ``write_tables``: per-row tables with
        borrowed (shared) entries sentineled so prefix blocks never take
        a write — defaults to ``cache["block_tables"]``.

        Returns ``(cache, chunk_logits (B, V), toks (B, n_steps))``:
        ``chunk_logits[b]`` is the logits at row ``b``'s last valid
        chunk position (sample tok0 from it when the chunk completes the
        prompt); ``toks`` are the decode samples (discard inactive
        rows').  Jit key is ``("mixed", C, n_steps)`` — compiled once
        per (chunk width, decode quantum), independent of every prompt
        length in flight.
        """
        if not self.paged:
            raise ValueError("mixed_step needs Engine(paged=True)")
        from repro.core.tracing import is_tracer
        # the span holds the lens guard: a device->host sync before the
        # enqueue
        with spans.span("engine.dispatch"):
            chunk_tokens = jnp.asarray(chunk_tokens, jnp.int32)
            b, c = chunk_tokens.shape
            nv = np.asarray(n_valid, np.int32)
            act = np.zeros((b,), bool) if decode_active is None \
                else np.asarray(decode_active, bool)
            lens = cache["lens"]
            if not is_tracer(lens):
                lens_np = np.asarray(lens)
                if (nv > 0).any():
                    hi = int((lens_np + nv)[nv > 0].max())
                    if hi > self.max_len:
                        raise ValueError(
                            f"mixed_step: prefill chunk frontier {hi} "
                            f"exceeds engine max_len {self.max_len}")
                if act.any():
                    hi = int(lens_np[act].max())
                    if hi + int(n_steps) > self.max_len:
                        raise ValueError(
                            f"mixed_step: decode frontier {hi} + "
                            f"{int(n_steps)} steps exceeds engine max_len "
                            f"{self.max_len}; retire rows first")
            wt = cache["block_tables"] if write_tables is None \
                else jnp.asarray(write_tables, jnp.int32)
            key = ("mixed", int(c), int(n_steps))
            fn = self._get_jit(self._decode_jit, key,
                               lambda: self._mixed_fn(int(n_steps)))
            cache, chunk_logits, toks, self._key = self._dispatch(
                fn, self.params, cache, chunk_tokens, jnp.asarray(nv),
                jnp.asarray(tokens, jnp.int32), self._key,
                jnp.asarray(act), wt)
            return self.shard_cache(cache), chunk_logits, toks

    # ------------------------------------------------------------------
    # prefix sharing: COW block copies + sanitizer poison (paged only)
    # ------------------------------------------------------------------

    def copy_blocks(self, cache, src_ids, dst_ids):
        """COW device half: duplicate arena blocks ``src_ids -> dst_ids``
        across every content leaf (posit patterns move verbatim, no
        dequantize round-trip).  Jit-specialized per copy count."""
        from repro.models import layers as L
        keys = ("c_kv", "k_rope") if self.cfg.mla else ("k", "v")

        def build():
            def arena_copy(cache, src, dst):
                out = dict(cache)
                for k in keys:
                    out[k] = L.paged_copy_blocks(cache[k], src, dst)
                return out
            return jax.jit(arena_copy)

        fn = self._get_jit(self._decode_jit, ("copy", len(src_ids)),
                           build)
        return self.shard_cache(self._dispatch(
            fn, cache, jnp.asarray(src_ids, jnp.int32),
            jnp.asarray(dst_ids, jnp.int32)))

    def poison_blocks(self, cache, ids):
        """Sanitizer device half: overwrite reclaimed arena blocks with
        the loud-but-finite poison pattern (``layers.paged_poison_blocks``)
        across every content leaf.  Jit-specialized per block count; a
        stale table entry pointing at a poisoned block corrupts logits
        visibly instead of silently serving freed KV."""
        if not ids:
            return cache
        from repro.models import layers as L
        keys = ("c_kv", "k_rope") if self.cfg.mla else ("k", "v")

        def build():
            def arena_poison(cache, ids):
                out = dict(cache)
                for k in keys:
                    out[k] = L.paged_poison_blocks(cache[k], ids)
                return out
            return jax.jit(arena_poison)

        fn = self._get_jit(self._decode_jit, ("poison", len(ids)), build)
        return self.shard_cache(self._dispatch(
            fn, cache, jnp.asarray(ids, jnp.int32)))

    # ------------------------------------------------------------------
    # decode: one lax.scan == one compiled call for the whole generation
    # ------------------------------------------------------------------

    def _decode_fn(self, n_steps: int):
        cfg, fam, temp = self.cfg, self.fam, self.temperature

        def decode_scan(params, cache, logits, key):
            tok0, key = sample_token(logits, key, temp)

            def step(carry, _):
                cache, tok, key = carry
                logits, cache = fam.decode_step(params, cache, tok, cfg)
                nxt, key = sample_token(logits, key, temp)
                return (cache, nxt, key), nxt

            (cache, _, key), toks = lax.scan(
                step, (cache, tok0, key), length=n_steps - 1)
            out = jnp.concatenate([tok0[None], toks], axis=0)  # (n,B)
            return cache, out.T, key

        return jax.jit(decode_scan)

    def _check_fits(self, padded_len: int, max_new_tokens: int):
        need = padded_len + max_new_tokens - 1        # last token not cached
        if need > self.max_len:
            raise ValueError(
                f"prompt ({padded_len}) + {max_new_tokens} new tokens "
                f"needs {need} cache slots > engine max_len {self.max_len}")

    def generate(self, prompts, max_new_tokens: int, *, frames=None,
                 visual=None) -> GenerationResult:
        """Prefill + scan-decode ``max_new_tokens`` tokens in ONE compiled
        decode call.  Raises up front if the request cannot fit in the
        preallocated ``max_len`` — out-of-capacity writes never clamp."""
        tokens, _ = self.pack_prompts(prompts)
        self._check_fits(tokens.shape[1], max_new_tokens)
        cache, logits, lens = self.prefill(
            prompts, frames=frames, visual=visual,
            reserve_tokens=max_new_tokens - 1)
        fn = self._get_jit(self._decode_jit, max_new_tokens,
                           lambda: self._decode_fn(max_new_tokens))
        cache, toks, self._key = self._dispatch(
            fn, self.params, cache, logits, self._key)
        return GenerationResult(tokens=np.asarray(toks),
                                prompt_lens=np.asarray(lens),
                                prefill_logits=np.asarray(logits),
                                cache=cache)

    def generate_stepwise(self, prompts, max_new_tokens: int, *,
                          frames=None, visual=None) -> GenerationResult:
        """Reference path: same sampling, but one jitted decode_step per
        token (Python-loop dispatch).  Produces tokens identical to
        ``generate`` — kept for tests and dispatch-overhead benchmarks."""
        tokens, _ = self.pack_prompts(prompts)
        self._check_fits(tokens.shape[1], max_new_tokens)
        cache, logits, lens = self.prefill(
            prompts, frames=frames, visual=visual,
            reserve_tokens=max_new_tokens - 1)
        fam, cfg = self.fam, self.cfg
        step_fn = self._get_jit(
            self._decode_jit, "step",
            lambda: jax.jit(lambda p, c, t: fam.decode_step(p, c, t, cfg)))
        key = self._key
        tok, key = sample_token(logits, key, self.temperature)
        outs = [tok]
        for _ in range(max_new_tokens - 1):
            step_logits, cache = self._dispatch(
                step_fn, self.params, cache, tok)
            tok, key = sample_token(step_logits, key, self.temperature)
            outs.append(tok)
        self._key = key
        return GenerationResult(
            tokens=np.stack([np.asarray(t) for t in outs], axis=1),
            prompt_lens=np.asarray(lens),
            prefill_logits=np.asarray(logits), cache=cache)
