"""Serving launcher: the preallocated ring-buffer posit-cache engine.

Loads (or random-inits) a model, builds a ``repro.runtime.engine.Engine``
with a ``--max-len`` cache budget, prefills a batch of prompts (ragged
lengths supported for the transformer family via ``--ragged``), then
decodes the whole generation in one compiled ``lax.scan`` call.
``--kv-posit`` turns on the paper's KV compression; the report prints
actual vs f32-equivalent cache bytes.

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-medium-14b \
      --reduced --batch 4 --prompt-len 32 --gen 16 --kv-posit posit16 \
      --max-len 64 --temperature 0.7 --seed 0

``--continuous`` switches to the iteration-level scheduler
(``repro.runtime.scheduler``) over a paged engine: ``--n-requests``
requests arrive on a simulated Poisson trace (``--arrival-rate``
expected arrivals per decode step), prompts/generation lengths are
ragged, and ``--batch`` becomes the slot-pool width.  Prompts flow
through the decode lane in ``--chunk-size``-token chunks and requests
join and leave between rounds; every round is ONE compiled dispatch
whose shape never depends on a prompt length, so the engine's compile
count stays flat.  The report prints goodput and p50/p99 request
latency in decode steps.

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-medium-14b \
      --reduced --continuous --batch 4 --n-requests 16 \
      --arrival-rate 0.2 --chunk-size 8 --max-len 64

The continuous scheduler always serves from the paged block-table
layout (``--block-size`` slots per block, ``--n-blocks`` arena size;
0 = worst case): rows allocate blocks as they grow and free them at
retirement, so peak cache memory tracks the tokens actually resident
instead of the worst case.  For the one-shot path ``--paged`` selects
that layout; omit it for the dense ``batch x max_len`` cache.

``--prefix-cache`` (with ``--continuous``) deduplicates shared
prompt prefixes: admission matches each prompt's leading full blocks
against a content-addressed index of resident blocks, borrows the hits
via refcounts and skips their prefill chunks; writes into borrowed
blocks copy-on-write first, so greedy token streams are unchanged.
``--prefix-share`` generates the matching trace — every prompt opens
with the same system prefix of that fractional length:

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-medium-14b \
      --reduced --continuous --prefix-cache --batch 4 \
      --n-requests 16 --prompt-len 32 --prefix-share 0.75 --block-size 4

``--deadline-ms`` attaches a completion
deadline to every request — admission turns earliest-deadline-first
and, when the pool is full, the scheduler preempts the latest-deadline
row (releasing its blocks) to admit a more urgent one.  Deadlines are
converted to the decode-step simulation clock at ``MS_PER_STEP`` ms
per step (an assumed reference-hardware step time; the SIMULATED
schedule is what the deadline shapes, wall time per step varies by
host):

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-medium-14b \
      --reduced --continuous --batch 4 \
      --n-requests 16 --deadline-ms 400 --chunk-size 4

``--model-parallel N`` serves tensor-parallel over a host mesh
(``launch.mesh.make_host_mesh``): weights are placed by the
``runtime/sharding.py`` rule table and the paged block arena is
head-sharded over the 'model' axis, so each device holds
``1/N``-th of the KV content (the report prints per-device KV bytes).
Token streams are identical to the single-device run.  Multi-device
CPU hosts are forced with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set it before
launching); a degree that does not divide the device count rounds
down with a warning.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import configs
from repro.compress.kvcache import cache_report
from repro.launch import compile_cache
from repro.models import get_family
from repro.models.layers import cdtype
from repro.runtime.engine import Engine
from repro.runtime.scheduler import Scheduler

# assumed wall time of one decode step on the reference hardware, used
# only to convert --deadline-ms into the decode-step simulation clock
# (the schedule is simulated, so only the RATIO deadline/step matters)
MS_PER_STEP = 10.0


def poisson_trace(rng, n_requests, rate, vocab, prompt_len, gen, *,
                  min_prompt_len=0, min_gen=0):
    """Ragged request trace: Poisson arrivals (``rate`` expected requests
    per decode step), uniform prompt lengths in ``[min_prompt_len,
    prompt_len]`` and generation lengths in ``[min_gen, gen]`` (a zero
    minimum means half the prompt length and a quarter of ``gen``)."""
    arrivals = np.cumsum(rng.exponential(1.0 / max(rate, 1e-9),
                                         size=n_requests))
    plo = max(2, min_prompt_len or prompt_len // 2)
    glo = max(2, min_gen or gen // 4)
    out = []
    for t in arrivals:
        plen = int(rng.integers(plo, prompt_len + 1))
        g = int(rng.integers(glo, gen + 1))
        out.append((float(t), rng.integers(1, vocab, plen).tolist(), g))
    return out


def shared_prefix_trace(rng, n_requests, rate, vocab, prompt_len, gen,
                        share: float = 0.75):
    """Request trace where every prompt opens with the SAME system
    prefix: ``share`` of ``prompt_len`` tokens are drawn once and
    reused, the tail is per-request.  Arrivals/generation lengths match
    :func:`poisson_trace`'s model; this is the trace prefix caching is
    built for (the share ratio bounds its possible win)."""
    arrivals = np.cumsum(rng.exponential(1.0 / max(rate, 1e-9),
                                         size=n_requests))
    n_shared = max(1, int(prompt_len * share))
    prefix = rng.integers(1, vocab, n_shared).tolist()
    out = []
    for t in arrivals:
        tail = int(rng.integers(2, max(3, prompt_len - n_shared + 1)))
        g = int(rng.integers(max(2, gen // 4), gen + 1))
        out.append((float(t),
                    prefix + rng.integers(1, vocab, tail).tolist(), g))
    return out


def drive_trace(sched: Scheduler, trace, deadline_steps=None):
    """Feed a (arrival_step, prompt, gen) trace through a scheduler,
    advancing the simulation clock through idle gaps; returns
    ``{rid: Completion}`` keyed in trace order.  ``deadline_steps``
    attaches ``arrival + deadline_steps`` as every request's absolute
    deadline (EDF admission + preemption; ``None`` = best-effort)."""
    pending = list(trace)
    done = {}
    order = {}
    while pending or sched.has_work:
        while pending and pending[0][0] <= sched.steps_run:
            t, prompt, gen = pending.pop(0)
            rid = sched.submit(
                prompt, gen,
                deadline=None if deadline_steps is None
                else int(np.ceil(t)) + int(deadline_steps))
            order[rid] = len(order)
        if not sched.has_work:
            # idle: jump the decode-step clock to the next arrival
            sched.steps_run = max(sched.steps_run,
                                  int(np.ceil(pending[0][0])))
            continue
        for c in sched.step():
            done[c.rid] = c
    return done, order


def _build_engine(args, cfg, params, max_len, paged):
    kernel = getattr(args, "decode_kernel", "gather")
    mesh = None
    if getattr(args, "model_parallel", 1) > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(args.model_parallel)
    return Engine(cfg, params, max_len=max_len,
                  temperature=args.temperature, seed=args.seed,
                  paged=paged, block_size=args.block_size,
                  n_blocks=args.n_blocks,
                  decode_kernel=None if kernel == "gather" else kernel,
                  mesh=mesh)


def run_continuous(args, cfg, params):
    rng = np.random.default_rng(args.seed)
    # worst-case slot demand: prompt + gen - 1 cached tokens plus a full
    # chunk of frontier headroom (overshoot before retirement)
    max_len = args.max_len or (args.prompt_len + args.gen - 1 +
                               args.chunk_size)
    engine = _build_engine(args, cfg, params, max_len, paged=True)
    sched = Scheduler(engine, n_slots=args.batch,
                      chunk_size=args.chunk_size,
                      prefix_cache=args.prefix_cache)
    deadline_steps = None
    if args.deadline_ms > 0:
        deadline_steps = max(1, int(np.ceil(args.deadline_ms
                                            / MS_PER_STEP)))
    if args.prefix_share > 0:
        trace = shared_prefix_trace(rng, args.n_requests,
                                    args.arrival_rate, cfg.vocab,
                                    args.prompt_len, args.gen,
                                    share=args.prefix_share)
    else:
        trace = poisson_trace(rng, args.n_requests, args.arrival_rate,
                              cfg.vocab, args.prompt_len, args.gen,
                              min_prompt_len=args.min_prompt_len,
                              min_gen=args.min_gen)
    t0 = time.time()
    done, _ = drive_trace(sched, trace, deadline_steps=deadline_steps)
    dt = time.time() - t0
    rep = cache_report(sched.cache)

    useful = sum(len(c.tokens) for c in done.values())
    lat = np.array(sorted(c.latency_steps for c in done.values()))
    goodput = useful / max(sched.steps_run, 1)
    print(f"continuous: {len(done)} requests, {useful} tokens in "
          f"{sched.n_chunks} chunks ({sched.steps_run} decode steps, "
          f"{dt:.2f}s incl. compile)")
    print(f"  goodput {goodput:.2f} tok/step of a {args.batch}-slot pool "
          f"({useful / max(dt, 1e-9):.1f} tok/s wall); latency p50 "
          f"{np.percentile(lat, 50):.0f} p99 {np.percentile(lat, 99):.0f} "
          f"steps")
    print(f"  cache: {rep['bytes']:,} bytes of {rep['f32_bytes']:,} "
          f"f32-equiv ({rep['ratio']:.2f}x, kv_posit={cfg.kv_posit}, "
          f"max_len={max_len})")
    print(f"  paged: {sched.n_blocks} arena blocks x "
          f"{sched.block_size} slots (dense worst case "
          f"{args.batch * sched.table_width}); peak in use "
          f"{sched.pool.peak_in_use}, peak committed "
          f"{sched.peak_committed}")
    if engine.mesh is not None:
        mp = engine.mesh.shape.get("model", 1)
        print(f"  sharded: mesh {dict(engine.mesh.shape)}; KV per "
              f"device {rep['per_device_bytes']:,} of {rep['bytes']:,} "
              f"bytes (model_parallel={mp}); step wall p50 "
              f"{sched.stats['step_wall_p50_ms']:.1f} ms p99 "
              f"{sched.stats['step_wall_p99_ms']:.1f} ms")
    print(f"  chunked prefill: {sched.prefill_tokens} prompt tokens "
          f"through the decode lane in {args.chunk_size}-token "
          f"chunks; {engine.n_compiles} compiled programs "
          f"(flat across prompt lengths)")
    if deadline_steps is not None:
        missed = sum(1 for c in done.values()
                     if c.finished_step > c.arrival_step + deadline_steps)
        print(f"  deadlines: {args.deadline_ms:.0f} ms "
              f"({deadline_steps} steps at {MS_PER_STEP:.0f} ms/step); "
              f"{len(done) - missed}/{len(done)} met, "
              f"{sched.n_preempted} preemptions")
    if args.prefix_cache:
        print(f"  prefix cache: {sched.prefix_hits}/{len(done)} "
              f"admissions hit, {sched.prefix_matched_tokens} prompt "
              f"tokens served from cache ({sched.prefill_tokens} "
              f"prefilled), {sched.n_cow} COW copies, "
              f"{sched.n_evicted} evictions; peak committed "
              f"physical {sched.peak_committed} vs logical "
              f"{sched.peak_logical} blocks")
    return done, sched


def init_serving_params(cfg, seed: int):
    """Random weights from ``seed``, made on the device directly in
    ``cfg.compute_dtype``: the jitted init casts each leaf as it is
    made, so a float32 copy of the whole model never exists (a
    published-width bf16 model may fit a chip whose memory its f32 copy
    would overflow)."""
    fam = get_family(cfg)
    dt = cdtype(cfg)

    def build(key):
        return jax.tree.map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, fam.init_params(key, cfg))

    return jax.jit(build)(jax.random.PRNGKey(seed))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS,
                    default="phi3-medium-14b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="serve only the first N layers at the "
                         "configuration's published widths (a depth cut "
                         "to fit the devices; 0 = all layers)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt lengths across the batch "
                         "(transformer family only)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--min-prompt-len", type=int, default=0,
                    help="with --continuous: shortest prompt of the trace "
                         "(0 = half of --prompt-len)")
    ap.add_argument("--min-gen", type=int, default=0,
                    help="with --continuous: fewest new tokens of a "
                         "request (0 = a quarter of --gen)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="preallocated cache length (default: "
                         "prompt-len + gen for one-shot, plus "
                         "chunk-size - 1 headroom with --continuous)")
    ap.add_argument("--kv-posit", choices=["posit16", "posit8", "none"],
                    default="none")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 = softmax sampling")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the random weights and the trace")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: requests arrive on a "
                         "simulated Poisson trace and join/leave between "
                         "decode chunks (transformer family only)")
    ap.add_argument("--arrival-rate", type=float, default=0.2,
                    help="expected request arrivals per decode step "
                         "(with --continuous)")
    ap.add_argument("--n-requests", type=int, default=16,
                    help="trace length (with --continuous)")
    ap.add_argument("--chunk-size", type=int, default=8,
                    help="decode steps between scheduling rounds "
                         "(with --continuous)")
    ap.add_argument("--paged", action="store_true",
                    help="one-shot path: paged block-table KV cache "
                         "(transformer family only); omit for the dense "
                         "layout.  --continuous is always paged")
    ap.add_argument("--block-size", type=int, default=16,
                    help="cache slots per arena block (paged)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="arena size in blocks (paged; "
                         "0 = worst case, never out of blocks)")
    ap.add_argument("--decode-kernel", choices=["gather", "fused"],
                    default="gather",
                    help="paged decode attention path: "
                         "'gather' materializes per-row KV via "
                         "paged_gather then attends in jnp; 'fused' "
                         "walks the block table inside one Pallas "
                         "kernel (posit decode + online softmax "
                         "in-kernel), token-identical with ~3-7x fewer "
                         "decode KV bytes")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-addressed prefix sharing with "
                         "copy-on-write block tables (with --continuous): "
                         "admissions borrow already-resident "
                         "prompt blocks and prefill only the unmatched "
                         "suffix; greedy token streams are unchanged")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="with --continuous: per-request completion "
                         "deadline in milliseconds, converted to the "
                         "decode-step simulation clock at MS_PER_STEP "
                         "ms per step; drives EDF admission and "
                         "preemption-by-block-release (0 = best-effort "
                         "FIFO)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="tensor-parallel degree over a host device "
                         "mesh: weights shard by the runtime/sharding "
                         "rule table and the paged KV arena shards its "
                         "head axis over 'model', so per-device KV "
                         "bytes drop ~linearly; token streams are "
                         "identical to the single-device run (force "
                         "devices on CPU with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--prefix-share", type=float, default=0.0,
                    help="with --continuous: fraction of each prompt "
                         "drawn from ONE shared system prefix (0 = fully "
                         "independent Poisson prompts); the share ratio "
                         "bounds the possible prefix-cache win")
    args = ap.parse_args(argv)
    if args.prefix_cache and not args.continuous:
        ap.error("--prefix-cache requires --continuous")
    if args.deadline_ms > 0 and not args.continuous:
        ap.error("--deadline-ms requires --continuous")
    if args.decode_kernel == "fused" and not (args.paged or
                                              args.continuous):
        ap.error("--decode-kernel fused requires a paged cache "
                 "(--paged or --continuous)")

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(compute_dtype="float32")
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    if args.kv_posit != "none":
        cfg = dataclasses.replace(cfg, kv_posit=args.kv_posit)

    compile_cache.enable()
    rng = np.random.default_rng(args.seed)
    params = init_serving_params(cfg, args.seed)

    if args.continuous:
        return run_continuous(args, cfg, params)

    if args.ragged:
        lens = rng.integers(max(2, args.prompt_len // 2),
                            args.prompt_len + 1, size=args.batch)
        prompts = [rng.integers(1, cfg.vocab, int(n)).tolist()
                   for n in lens]
    else:
        prompts = rng.integers(1, cfg.vocab,
                               size=(args.batch, args.prompt_len))

    kwargs = {}
    if cfg.family == "whisper":
        kwargs["frames"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.encoder_seq, cfg.d_model)), jnp.float32)
    if cfg.n_visual_tokens:
        kwargs["visual"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_visual_tokens, cfg.d_model)), jnp.float32)

    max_len = args.max_len or (args.prompt_len + args.gen)
    engine = _build_engine(args, cfg, params, max_len, paged=args.paged)

    t0 = time.time()
    cache, logits, lens = engine.prefill(
        prompts, reserve_tokens=args.gen - 1, **kwargs)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    rep = cache_report(cache)
    print(f"prefill: {args.batch} prompts (lens {lens.tolist()}) in "
          f"{t_prefill:.2f}s; cache bytes = {rep['bytes']:,} of "
          f"{rep['f32_bytes']:,} f32-equiv ({rep['ratio']:.2f}x, "
          f"kv_posit={cfg.kv_posit}, max_len={max_len})")

    t0 = time.time()
    res = engine.generate(prompts, args.gen, **kwargs)
    dt = time.time() - t0
    print(f"decode: {args.gen} steps in {dt:.2f}s "
          f"({args.gen * args.batch / max(dt, 1e-9):.1f} tok/s, "
          f"one compiled scan; includes prefill+compile on first call)")
    print("generated ids:\n", res.tokens)
    return res.tokens


if __name__ == "__main__":
    main()
