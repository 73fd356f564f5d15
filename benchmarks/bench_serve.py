"""Serving engine throughput: prefill + scan-decode tok/s by KV format,
plus continuous-vs-static batching goodput on a ragged arrival trace.

For each KV-cache storage format (f32 ``none``, ``posit16``, ``posit8``)
on a reduced transformer config, times the engine's jitted prefill and
its single-``lax.scan`` decode, and compares the scan against the
per-step jitted Python loop (dispatch overhead) once for the f32 cache.

The continuous-batching section replays the SAME Poisson trace (ragged
prompt and generation lengths) through (a) static batching — groups of
``n_slots`` requests that prefill together once the whole group has
arrived and decode ``max(gen)`` steps for everyone — and (b) the
iteration-level scheduler over a paged engine, which retires rows at
EOS/max-tokens and admits queued prompts between rounds, each request's
stream checked against its isolated ``Engine.generate``.  Both serve the
same useful-token demand on the same simulated clock (1 tick = 1 decode
step; batch-formation waits, arrival gaps and prefill tick too, prefill
at the scheduler's rate of ``chunk`` prompt tokens per ``chunk``-step
round on both sides), so goodput =
useful tokens / makespan compares what a user actually sees; the run
asserts continuous wins.  Executed-step utilization is also reported —
static can look "efficient" there precisely because its requests sit in
queues instead of slots.

Emits ``name,us_per_call,derived`` rows (harness contract); ``derived``
carries decode tok/s, the cache compression ratio, the scan-vs-stepwise
token agreement (expected 1.0), and for the batching comparison the
goodput and p50/p99 request latency in decode steps.

The paged-cache section replays the same trace through the paged
(block-table) scheduler: pass 1 sizes the block arena from the trace's
committed-blocks high-water mark, pass 2 reruns on that right-sized
arena and asserts every stream matches its isolated generation, with
strictly fewer cache bytes than a dense ``slots x max_len`` pool.
Pass 3 reruns the right-sized arena decoding through the FUSED Pallas
paged-attention kernel (``kernels/posit_paged_attn.py``) and asserts
token/schedule identity with pass 2, plus — the ROADMAP's decode-bytes
ask — reports analytic decode KV bytes/token for both paths and asserts
the fused kernel moves strictly fewer bytes than gather+dequant.

The prefix-caching section replays a SHARED-prefix trace (every prompt
opens with the same system prefix) through the paged scheduler with and
without ``prefix_cache=True`` and asserts token identity with strictly
fewer prefill tokens and strictly fewer peak physical blocks — the
dedup win, dropping roughly with the share ratio.

The chunked-prefill section replays an OVERLOAD Poisson trace (arrival
rate far above drain capacity, ragged prompt lengths) through the
scheduler on all three attention lanes (dense, MLA, sliding-window),
which serves every request through ONE compiled ``mixed_step`` shape.
Every stream must match its isolated generation and the engine
compile count must stay FLAT after warmup, whatever the novel prompt
lengths; the row reports the p99 WALL-CLOCK request latency.

The sharded section replays one paged trace through a
single-device engine and a tensor-parallel engine over a host device
mesh (weights by the ``runtime/sharding.py`` rule table, the KV block
arena head-sharded over 'model') and asserts per-request token AND
schedule identity — sharding must be invisible to the trace — plus the
point of the exercise: each device holds ~1/mp of the arena content
bytes, within one block of slack.  Needs >= 2 devices; on CPU force
them with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--smoke`` shrinks the sweep for the CI fast lane (exercises prefill
headroom, ring-free dense decode, both posit codecs, and the
continuous-batching scheduler end to end); ``--paged`` runs ONLY the
paged-arena section (the fast lane's paged smoke),
``--prefix-share`` adds (or alone, runs only) the prefix-caching
comparison, ``--chunked`` runs ONLY the chunked-prefill section,
and ``--sharded`` runs ONLY the tensor-parallel comparison.
``--sanitize`` arms the arena sanitizer on the paged, prefix, chunked
and sharded passes (``BlockPool(sanitize=True)`` misuse checks,
pre-chunk write gates, poisoned reclaims) and asserts the traces end
leak-free — the CI smoke runs with it so every PR replays the serving
trace under the sanitizer.
"""
from __future__ import annotations

import dataclasses
import math
import sys
import time

import numpy as np

import jax

from repro import configs
from repro.compress.kvcache import cache_report
from repro.launch.serve import (drive_trace, poisson_trace,
                                shared_prefix_trace)
from repro.models import get_family
from repro.runtime.engine import Engine
from repro.runtime.scheduler import Scheduler

ARCH = "phi3-medium-14b"
KV_FORMATS = (None, "posit16", "posit8")
REPEATS = 3


def _check_isolated(cfg, params, max_len, trace, done, order, what):
    """Every completion of a ``drive_trace`` run must equal its request's
    isolated greedy ``Engine.generate`` stream."""
    ref = Engine(cfg, params, max_len=max_len, seed=0)
    assert len(done) == len(trace)
    for rid, i in order.items():
        _, prompt, gen = trace[i]
        want = ref.generate([prompt], gen).tokens[0]
        assert (done[rid].tokens == want).all(), \
            f"{what} diverged from isolated generation on request {rid}"


def _time(fn):
    jax.block_until_ready(fn())           # compile + warm cache
    t0 = time.perf_counter()
    out = None
    for _ in range(REPEATS):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPEATS * 1e6


def run(smoke: bool = False, paged: bool = True):
    batch, prompt_len, gen = (2, 16, 8) if smoke else (4, 32, 32)
    base = configs.get_config(ARCH).reduced(compute_dtype="float32")
    rng = np.random.default_rng(7)
    params = get_family(base).init_params(jax.random.PRNGKey(0), base)
    prompts = rng.integers(1, base.vocab, size=(batch, prompt_len))

    rows = []
    stepwise_tokens = None
    for kv in KV_FORMATS:
        cfg = dataclasses.replace(base, kv_posit=kv)
        eng = Engine(cfg, params, max_len=prompt_len + gen, seed=0)

        us_prefill = _time(lambda: eng.prefill(prompts)[1])
        cache, _, _ = eng.prefill(prompts)
        rep = cache_report(cache)
        rows.append((f"serve_prefill_kv={kv or 'none'}_b{batch}"
                     f"_s{prompt_len}", us_prefill,
                     f"cache_bytes={rep['bytes']} "
                     f"ratio={rep['ratio']:.2f}x"))

        us_gen = _time(lambda: eng.generate(prompts, gen).tokens)
        tok_s = gen * batch / (us_gen / 1e6)
        derived = f"tok_s={tok_s:.1f} gen={gen}"
        if kv is None:
            # dispatch-overhead reference: per-step jitted Python loop
            us_step = _time(
                lambda: eng.generate_stepwise(prompts, gen).tokens)
            agree = float((eng.generate(prompts, gen).tokens ==
                           eng.generate_stepwise(prompts, gen).tokens)
                          .mean())
            stepwise_tokens = agree
            derived += (f" stepwise_us={us_step:.1f} "
                        f"scan_speedup={us_step / max(us_gen, 1e-9):.2f}x "
                        f"scan_vs_step_match={agree:.4f}")
        rows.append((f"serve_decode_kv={kv or 'none'}_b{batch}"
                     f"_g{gen}", us_gen, derived))
    assert stepwise_tokens == 1.0, \
        "scan decode diverged from the per-step reference loop"
    rows.extend(run_batching_comparison(smoke=smoke))
    if paged:
        rows.extend(run_paged_comparison(smoke=smoke))
        rows.extend(run_prefix_comparison(smoke=smoke))
        rows.extend(run_chunked_comparison(smoke=smoke))
        rows.extend(run_sharded_comparison(smoke=smoke))
    return rows


def _static_batching(cfg, params, trace, n_slots, max_len, chunk):
    """Static batching baseline: requests group in arrival order, a group
    prefills only once its LAST member has arrived, and every row decodes
    ``max(gen)`` steps — the padding/idle waste continuous batching
    removes.  The group's prefill is charged on the clock at the rate
    the scheduler pays for its own (a prompt goes through ``chunk``
    tokens per ``chunk``-step round), so both sides count prefill the
    same way.  Returns (useful_tokens, executed_steps, latencies,
    makespan_steps, wall_s).
    """
    eng = Engine(cfg, params, max_len=max_len, seed=0)
    clock = 0.0                      # decode-step simulation clock
    useful, steps, lats = 0, 0, []
    t0 = time.perf_counter()
    for i in range(0, len(trace), n_slots):
        group = trace[i:i + n_slots]
        start = max(clock, max(t for t, _, _ in group))
        gen_max = max(g for _, _, g in group)
        prefill = -(-max(len(p) for _, p, _ in group) // chunk) * chunk
        eng.generate([p for _, p, _ in group], gen_max)
        steps += prefill + gen_max
        clock = start + prefill + gen_max
        for t, _, g in group:
            useful += g              # only the requested tokens count
            lats.append(clock - t)
    return useful, steps, lats, clock, time.perf_counter() - t0


def run_batching_comparison(smoke: bool = False):
    """Continuous vs static batching on one ragged Poisson trace."""
    # arrival rates chosen to keep the pool under load (arrivals at or
    # above drain capacity): an idle pool makes every scheduler look the
    # same because the makespan is arrival-tail-bound, not service-bound
    # chunk size trades scheduling overhead against retirement/admission
    # granularity: a finished row overshoots by up to chunk-1 steps, so
    # big chunks erode the win on short ragged generations
    if smoke:
        n_req, n_slots, plen, gen, chunk, rate = 8, 2, 8, 8, 4, 1.0
    else:
        n_req, n_slots, plen, gen, chunk, rate = 24, 4, 16, 16, 4, 1.2
    max_len = plen + gen - 1 + chunk
    cfg = configs.get_config(ARCH).reduced(compute_dtype="float32")
    params = get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)
    trace = poisson_trace(np.random.default_rng(11), n_req, rate,
                          cfg.vocab, plen, gen)

    s_useful, s_steps, s_lat, s_makespan, s_wall = _static_batching(
        cfg, params, trace, n_slots, max_len, chunk)
    s_goodput = s_useful / max(s_makespan, 1e-9)

    eng = Engine(cfg, params, max_len=max_len, seed=0, paged=True,
                 block_size=4)
    sched = Scheduler(eng, n_slots=n_slots, chunk_size=chunk)
    t0 = time.perf_counter()
    done, order = drive_trace(sched, trace)
    c_wall = time.perf_counter() - t0
    _check_isolated(cfg, params, max_len, trace, done, order,
                    "continuous batching")
    c_useful = sum(len(c.tokens) for c in done.values())
    c_steps = sched.n_chunks * sched.chunk_size
    c_makespan = max(c.finished_step for c in done.values())
    c_goodput = c_useful / max(c_makespan, 1e-9)
    c_lat = [c.latency_steps for c in done.values()]

    rows = [
        (f"serve_static_batch_b{n_slots}_n{n_req}", s_wall * 1e6,
         f"goodput_tok_per_step={s_goodput:.2f} "
         f"useful={s_useful} makespan={s_makespan:.0f} "
         f"util={s_useful / (s_steps * n_slots):.2f} "
         f"lat_p50={np.percentile(s_lat, 50):.0f} "
         f"lat_p99={np.percentile(s_lat, 99):.0f}"),
        (f"serve_continuous_b{n_slots}_n{n_req}_c{chunk}", c_wall * 1e6,
         f"goodput_tok_per_step={c_goodput:.2f} "
         f"useful={c_useful} makespan={c_makespan} "
         f"util={c_useful / (c_steps * n_slots):.2f} "
         f"lat_p50={np.percentile(c_lat, 50):.0f} "
         f"lat_p99={np.percentile(c_lat, 99):.0f} "
         f"goodput_gain={c_goodput / max(s_goodput, 1e-9):.2f}x"),
    ]
    assert c_useful == s_useful, \
        "the two batching modes served different token demand"
    assert c_goodput > s_goodput, (
        f"continuous batching goodput {c_goodput:.3f} tok/step did not "
        f"beat static batching {s_goodput:.3f} on the ragged trace")
    return rows


def run_paged_comparison(smoke: bool = False, sanitize: bool = False):
    """The paged (block-table) scheduler on one ragged trace.

    Two gather passes: the first (worst-case arena, no deferrals
    possible) measures the trace's committed-blocks high-water mark;
    the second replays on an arena of exactly that size — reservations
    still never defer, so scheduling is identical — and every stream
    must match its isolated generation, on strictly fewer cache bytes
    than a dense ``slots x max_len`` pool.
    """
    if smoke:
        n_req, n_slots, plen, gen, chunk, rate = 8, 2, 8, 8, 4, 1.0
    else:
        n_req, n_slots, plen, gen, chunk, rate = 24, 4, 16, 16, 4, 1.2
    block = 4
    # the dense pool must budget max_len for the WORST request (plus
    # chunk overshoot) with slack for anything longer; paged rows only
    # ever commit their own actual need, so with >= 2 blocks of dense
    # slack the byte win below holds for ANY trace, not by seed luck
    max_len = plen + gen - 1 + chunk + 2 * block
    cfg = configs.get_config(ARCH).reduced(compute_dtype="float32")
    params = get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)
    trace = poisson_trace(np.random.default_rng(11), n_req, rate,
                          cfg.vocab, plen, gen)

    l_bytes = cache_report(get_family(cfg).init_cache(
        cfg, n_slots, max_len))["bytes"]  # the dense slots x max_len pool

    # pass 1: worst-case arena -> the trace's committed-block peak
    probe = Scheduler(Engine(cfg, params, max_len=max_len, seed=0,
                             paged=True, block_size=block),
                      n_slots=n_slots, chunk_size=chunk)
    done_1, _ = drive_trace(probe, trace)
    n_blocks = probe.peak_committed

    # pass 2: right-sized arena (identical scheduling, fewer bytes);
    # --sanitize arms the arena sanitizer here, asserting the trace is
    # leak-free under the tightest pool the trace admits
    pag = Scheduler(Engine(cfg, params, max_len=max_len, seed=0,
                           paged=True, block_size=block,
                           n_blocks=n_blocks, sanitize=sanitize),
                    n_slots=n_slots, chunk_size=chunk)
    t0 = time.perf_counter()
    done_p, order = drive_trace(pag, trace)
    p_wall = time.perf_counter() - t0
    p_bytes = cache_report(pag.cache)["bytes"]
    _check_isolated(cfg, params, max_len, trace, done_p, order,
                    "paged scheduler")
    if sanitize:
        assert pag.n_leaked == 0 and not pag.leak_report(), \
            f"sanitizer found leaked arena blocks: {pag.leak_report()}"

    # pass 3: same right-sized arena, decoding through the FUSED Pallas
    # paged-attention kernel (block-table walk, posit decode in-kernel,
    # online softmax in VMEM) — must be token/schedule-identical to the
    # gather path while moving strictly fewer KV bytes per decoded token
    fus = Scheduler(Engine(cfg, params, max_len=max_len, seed=0,
                           paged=True, block_size=block,
                           n_blocks=n_blocks, sanitize=sanitize,
                           decode_kernel="fused"),
                    n_slots=n_slots, chunk_size=chunk)
    t0 = time.perf_counter()
    done_f, _ = drive_trace(fus, trace)
    f_wall = time.perf_counter() - t0

    assert done_1.keys() == done_p.keys() == done_f.keys()
    for rid in done_p:
        assert done_p[rid].finished_step == done_1[rid].finished_step
        assert (done_f[rid].tokens == done_p[rid].tokens).all(), \
            f"fused paged decode diverged from gather on request {rid}"
        assert done_f[rid].finished_step == done_p[rid].finished_step
    # per-request identity above implies useful tokens, makespan and
    # therefore goodput are EXACTLY equal — "no goodput regression" is
    # the identity check; only wall-clock can differ between the two
    useful = sum(len(c.tokens) for c in done_p.values())
    makespan = max(c.finished_step for c in done_p.values())
    goodput = useful / max(makespan, 1e-9)
    assert p_bytes < l_bytes, (
        f"paged arena ({p_bytes} B) not smaller than the dense "
        f"slots x max_len pool ({l_bytes} B)")
    dense_blocks = n_slots * pag.table_width

    # decode bytes/token ledger (ROADMAP: report alongside tok/s): the
    # fused kernel reads KV patterns from HBM once; the gather path
    # reads the arena, round-trips the gathered copy and (for posit KV)
    # the dequantized cache on top.  The CI smoke gates the strict win
    # for the serving config AND the posit16 KV cache it exists for.
    from repro.kernels.posit_paged_attn import paged_decode_kv_bytes
    tw, bs = pag.table_width, block
    bytes_parts = []
    for kv in (cfg.kv_posit, "posit16"):
        kcfg = dataclasses.replace(cfg, kv_posit=kv)
        b_f = paged_decode_kv_bytes(kcfg, tw, bs, kernel="fused")
        b_g = paged_decode_kv_bytes(kcfg, tw, bs, kernel="gather")
        assert b_f < b_g, (
            f"fused paged decode must move strictly fewer KV bytes than "
            f"gather+dequant (kv={kv}: {b_f} vs {b_g})")
        tag = kv or "none"
        bytes_parts.append(f"decode_kv_B_tok_fused_{tag}={b_f} "
                           f"decode_kv_B_tok_gather_{tag}={b_g} "
                           f"decode_bytes_saved_{tag}="
                           f"{1 - b_f / b_g:.2f}")
    return [
        (f"serve_paged_b{n_slots}_n{n_req}_c{chunk}_blk{block}",
         p_wall * 1e6,
         f"goodput_tok_per_step={goodput:.2f} "
         f"peak_cache_bytes={p_bytes} dense_cache_bytes={l_bytes} "
         f"bytes_saved={1 - p_bytes / l_bytes:.2f} "
         f"arena_blocks={n_blocks} worst_case_blocks={dense_blocks} "
         f"peak_blocks_in_use={pag.pool.peak_in_use}"),
        (f"serve_paged_fused_b{n_slots}_n{n_req}_c{chunk}_blk{block}",
         f_wall * 1e6,
         f"tokens_match_gather=1.0 " + " ".join(bytes_parts) + " "
         f"wall_vs_gather={f_wall / max(p_wall, 1e-9):.2f}x"),
    ]


def run_prefix_comparison(smoke: bool = False, sanitize: bool = False):
    """Prefix caching vs plain paging on a shared-prefix trace.

    Every prompt opens with the same system prefix (share ratio ~0.75),
    the regime prefix caching is built for.  The prefix-cached pass must
    reproduce the non-sharing paged pass token for token while
    prefilling strictly fewer tokens and committing strictly fewer peak
    PHYSICAL blocks — both dropping roughly with the share ratio (the
    matched prefix is stored once instead of once per resident sharer).

    Both passes first run a WARM-UP donor request (the bare system
    prefix) to completion before the timed trace.  Chunked admission
    registers a prompt's blocks only once its prefill finishes, so a
    cold index plus a dense arrival burst means the first ``n_slots``
    requests all prefill concurrently with nothing to share — and
    since ``peak_committed`` is a trace-wide max, that cold-start burst
    would pin both passes to the same worst-case peak and hide the
    steady-state win this benchmark exists to measure.  The donor makes
    the prefix resident (index-held, evictable) up front, which is the
    serving regime the docstring above describes.
    """
    if smoke:
        n_req, n_slots, plen, gen, chunk, rate = 8, 2, 16, 8, 4, 1.0
    else:
        n_req, n_slots, plen, gen, chunk, rate = 24, 4, 32, 16, 4, 1.2
    block, share = 4, 0.75
    max_len = plen + gen - 1 + chunk
    cfg = configs.get_config(ARCH).reduced(compute_dtype="float32")
    params = get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)
    trace = shared_prefix_trace(np.random.default_rng(13), n_req, rate,
                                cfg.vocab, plen, gen, share=share)
    # the donor prompt is exactly the shared system prefix (same
    # formula as shared_prefix_trace's n_shared)
    donor = trace[0][1][:max(1, int(plen * share))]

    def _warm(sched):
        donor_rid = sched.submit(list(donor), 1)
        while sched.has_work:
            sched.step()
        sched.steps_run = 0            # replay arrivals as authored
        return donor_rid

    base = Scheduler(Engine(cfg, params, max_len=max_len, seed=0,
                            paged=True, block_size=block),
                     n_slots=n_slots, chunk_size=chunk)
    _warm(base)
    t0 = time.perf_counter()
    done_b, _ = drive_trace(base, trace)
    b_wall = time.perf_counter() - t0

    # --sanitize arms the arena sanitizer on the sharing pass (the one
    # with COW/refcount invariants to violate) and asserts leak-freedom
    pfx = Scheduler(Engine(cfg, params, max_len=max_len, seed=0,
                           paged=True, block_size=block,
                           sanitize=sanitize),
                    n_slots=n_slots, chunk_size=chunk, prefix_cache=True)
    _warm(pfx)
    t0 = time.perf_counter()
    done_p, _ = drive_trace(pfx, trace)
    p_wall = time.perf_counter() - t0
    if sanitize:
        assert pfx.n_leaked == 0 and not pfx.leak_report(), \
            f"sanitizer found leaked arena blocks: {pfx.leak_report()}"

    assert done_b.keys() == done_p.keys()
    for rid in done_b:
        assert (done_p[rid].tokens == done_b[rid].tokens).all(), \
            f"prefix caching changed the tokens of request {rid}"
    assert pfx.prefix_hits > 0, "shared-prefix trace produced no hits"
    assert pfx.prefill_tokens < base.prefill_tokens, (
        f"prefix caching did not cut prefill work "
        f"({pfx.prefill_tokens} vs {base.prefill_tokens} tokens)")
    assert pfx.peak_committed < base.peak_committed, (
        f"prefix caching did not cut peak physical blocks "
        f"({pfx.peak_committed} vs {base.peak_committed})")
    return [
        (f"serve_prefix_b{n_slots}_n{n_req}_share{share}",
         p_wall * 1e6,
         f"prefill_tokens={pfx.prefill_tokens} "
         f"baseline_prefill_tokens={base.prefill_tokens} "
         f"prefill_saved={1 - pfx.prefill_tokens / base.prefill_tokens:.2f} "
         f"peak_physical_blocks={pfx.peak_committed} "
         f"baseline_peak_blocks={base.peak_committed} "
         f"peak_logical_blocks={pfx.peak_logical} "
         f"prefix_hits={pfx.prefix_hits} cow_copies={pfx.n_cow} "
         f"evictions={pfx.n_evicted} "
         f"wall_vs_paged={p_wall / max(b_wall, 1e-9):.2f}x"),
    ]


def _lane_cfg(lane):
    if lane == "mla":
        return configs.get_config("minicpm3-4b").reduced(
            compute_dtype="float32")
    cfg = configs.get_config(ARCH).reduced(compute_dtype="float32")
    if lane == "window":
        cfg = dataclasses.replace(cfg, sliding_window=8, attn_chunk_kv=8)
    return cfg


def _drive_wall(sched, trace):
    """Like :func:`drive_trace` but records each request's WALL-CLOCK
    latency (submit -> completion), the number a compile stall actually
    inflates; returns ``(done, {rid: seconds})``."""
    pending = list(trace)
    done, t_sub, lat = {}, {}, {}
    while pending or sched.has_work:
        while pending and pending[0][0] <= sched.steps_run:
            _, prompt, gen = pending.pop(0)
            rid = sched.submit(prompt, gen)
            t_sub[rid] = time.perf_counter()
        if not sched.has_work:
            sched.steps_run = max(sched.steps_run,
                                  int(np.ceil(pending[0][0])))
            continue
        for c in sched.step():
            done[c.rid] = c
            lat[c.rid] = time.perf_counter() - t_sub[c.rid]
    return done, lat


def run_chunked_comparison(smoke: bool = False, sanitize: bool = False):
    """Chunked prefill under an overload Poisson trace, on all three
    attention lanes.

    The arrival rate is far above drain capacity, so the pool is
    saturated and every admission stall lands on someone's tail
    latency.  The trace's ragged prompt lengths all go through the warm
    ``mixed_step`` program.  Asserts per-request identity with isolated
    generation, a flat post-warmup compile count (without the
    sanitizer, whose poison dispatches legitimately jit per reclaim
    size), and, under the sanitizer, a leak-free trace; reports the p99
    wall latency.
    """
    if smoke:
        n_req, n_slots, plen, gen, chunk = 8, 2, 12, 6, 4
    else:
        n_req, n_slots, plen, gen, chunk = 16, 4, 24, 12, 4
    block, rate = 4, 4.0               # rate >> drain: overload regime
    max_len = plen + gen - 1 + chunk
    rows = []
    for lane in ("dense", "mla", "window"):
        cfg = _lane_cfg(lane)
        params = get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)
        trace = poisson_trace(np.random.default_rng(17), n_req, rate,
                              cfg.vocab, plen, gen)
        warm = [(0.0, list(range(1, chunk + 2)), 2)]  # compile warmup

        eng = Engine(cfg, params, max_len=max_len, seed=0, paged=True,
                     block_size=block, sanitize=sanitize)
        sched = Scheduler(eng, n_slots=n_slots, chunk_size=chunk)
        _drive_wall(sched, warm)       # exclude warmup compiles
        warm_compiles = eng.n_compiles
        done, lat = _drive_wall(sched, trace)
        # rids follow the trace's submission order
        _check_isolated(cfg, params, max_len, trace, done,
                        dict(zip(sorted(done), range(len(trace)))),
                        f"chunked prefill ({lane} lane)")
        if not sanitize:
            assert eng.n_compiles == warm_compiles, (
                f"engine compiled {eng.n_compiles - warm_compiles} new "
                f"programs after warmup on the {lane} lane")
        if sanitize:
            assert sched.n_leaked == 0 and not sched.leak_report()
        p99 = float(np.percentile(list(lat.values()), 99))
        rows.append((
            f"serve_chunked_{lane}_b{n_slots}_n{n_req}_c{chunk}",
            p99 * 1e6,
            f"p99_wall_ms={p99 * 1e3:.1f} compiles={eng.n_compiles}"))
    return rows


def run_sharded_comparison(smoke: bool = False, sanitize: bool = False):
    """Tensor-parallel vs single-device serving on one paged trace.

    Builds a host mesh over all local devices with the largest 'model'
    degree dividing both the device count and the config's KV heads,
    replays the SAME paged trace through a
    single-device engine and a mesh engine (weights by the
    ``runtime/sharding.py`` rule table, arena heads on 'model'), and
    asserts per-request token AND schedule identity — sharding the
    arena must be invisible to the trace.  The byte ledger then gates
    the point of the exercise: each device's arena CONTENT footprint is
    at most ``content / mp`` plus one block of slack (replicated
    block-table metadata rides on every shard and is accounted
    separately).  Needs >= 2 devices (on CPU force them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``); returns no
    rows otherwise, which the baseline delta machinery tolerates.
    """
    n_dev = len(jax.devices())
    heads = 4                          # a head count mp can divide
    mp = math.gcd(n_dev, heads)
    if mp < 2:
        print(f"# serve_sharded: skipped ({n_dev} device(s); force "
              "more with XLA_FLAGS=--xla_force_host_platform_device_"
              "count=8)", file=sys.stderr, flush=True)
        return []
    if smoke:
        n_req, n_slots, plen, gen, chunk, rate = 8, 2, 8, 8, 4, 1.0
    else:
        n_req, n_slots, plen, gen, chunk, rate = 16, 4, 16, 16, 4, 1.2
    block = 4
    max_len = plen + gen - 1 + chunk
    cfg = dataclasses.replace(
        configs.get_config(ARCH).reduced(compute_dtype="float32"),
        n_heads=heads, n_kv_heads=heads)
    params = get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)
    trace = poisson_trace(np.random.default_rng(11), n_req, rate,
                          cfg.vocab, plen, gen)

    def _pass(mesh):
        eng = Engine(cfg, params, max_len=max_len, seed=0, paged=True,
                     block_size=block, sanitize=sanitize, mesh=mesh)
        sched = Scheduler(eng, n_slots=n_slots, chunk_size=chunk)
        t0 = time.perf_counter()
        done, _ = drive_trace(sched, trace)
        return done, sched, time.perf_counter() - t0

    done_1, _, base_wall = _pass(None)
    from repro.launch.mesh import make_host_mesh
    done_s, sched_s, s_wall = _pass(make_host_mesh(mp))

    assert done_1.keys() == done_s.keys()
    for rid in done_1:
        assert (done_s[rid].tokens == done_1[rid].tokens).all(), \
            f"sharded serving changed the tokens of request {rid}"
        assert done_s[rid].finished_step == done_1[rid].finished_step, \
            f"sharded serving changed the schedule of request {rid}"
    if sanitize:
        assert sched_s.n_leaked == 0 and not sched_s.leak_report(), \
            f"sanitizer found leaked arena blocks: {sched_s.leak_report()}"

    spec = sched_s.cache["k"].sharding.spec
    assert "model" in spec, \
        f"arena k is not head-sharded over 'model': spec={spec}"
    rep = cache_report(sched_s.cache)
    content = sum(int(np.prod(sched_s.cache[k].shape)) *
                  sched_s.cache[k].dtype.itemsize for k in ("k", "v"))
    meta = rep["bytes"] - content
    per_dev_content = rep["per_device_bytes"] - meta
    n_blocks = sched_s.cache["k"].shape[1]
    one_block = content // n_blocks
    assert per_dev_content <= content // mp + one_block, (
        f"per-device arena content {per_dev_content} B exceeds "
        f"content/mp + one block ({content // mp} + {one_block} B) "
        f"at model_parallel={mp}")
    stats = sched_s.stats
    return [
        (f"serve_sharded_mp{mp}_b{n_slots}_n{n_req}_c{chunk}",
         s_wall * 1e6,
         f"tokens_match_single_device=1.0 model_parallel={mp} "
         f"n_devices={n_dev} "
         f"per_device_kv_bytes={rep['per_device_bytes']} "
         f"total_kv_bytes={rep['bytes']} "
         f"per_device_content_bytes={per_dev_content} "
         f"content_bytes={content} "
         f"content_shard_frac={per_dev_content / content:.3f} "
         f"step_wall_p50_ms={stats['step_wall_p50_ms']:.1f} "
         f"step_wall_p99_ms={stats['step_wall_p99_ms']:.1f} "
         f"wall_vs_single={s_wall / max(base_wall, 1e-9):.2f}x"),
    ]


if __name__ == "__main__":
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    sanitize = "--sanitize" in argv
    print("name,us_per_call,derived")
    sections = [f for f in ("--paged", "--prefix-share", "--chunked",
                            "--sharded")
                if f in argv]
    if sections:                       # run ONLY the named sections
        rows = []
        if "--paged" in argv:
            rows += run_paged_comparison(smoke=smoke, sanitize=sanitize)
        if "--prefix-share" in argv:
            rows += run_prefix_comparison(smoke=smoke, sanitize=sanitize)
        if "--chunked" in argv:
            rows += run_chunked_comparison(smoke=smoke, sanitize=sanitize)
        if "--sharded" in argv:
            rows += run_sharded_comparison(smoke=smoke, sanitize=sanitize)
    else:
        rows = run(smoke=smoke, paged=not smoke)
        if smoke:
            rows += run_prefix_comparison(smoke=smoke, sanitize=sanitize)
    for row in rows:
        print(",".join(str(x) for x in row))
