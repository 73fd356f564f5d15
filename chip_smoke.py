#!/usr/bin/env python3
"""Bring-up smoke on a TPU: the paper's PVU ops and the posit serving
stack, driven through the entry points a user calls.

    python chip_smoke.py              # one chip: PVU ops, minicpm3-4b serving
    python chip_smoke.py --chips 4    # four chips: tensor-parallel serving

One process holds the chip(s) for the whole run.  Phases:

* PVU ops (the paper): ``ops.vadd/vsub/vmul/vdiv`` (both dividers) and
  ``ops.dot`` as native Pallas kernels — exhaustively over all 256 x 256
  posit8 pairs against ``core.softposit_ref`` (the accuracy table:
  add/sub/mul/dot/exact-div 100 %, nr3-div >= the paper's 95.84 %), and
  on 2^20 random posit16 pairs bit-compared with ``kernels/ref.py`` run
  on the same chip (plus ``ops.pgemm`` on a small product).
* Serving: minicpm3-4b at its published widths, all 62 layers, bf16
  weights from ``--seed``, posit16 KV, through ``launch/serve.py``'s
  ``main()`` with ``--continuous``: 8 slots, 16 requests, prompts of
  512-2048 tokens, 64-128 new tokens, ``max_len`` 4096, blocks of 16.  Once with the fused decode kernel and once with
  the gather path; the runs are compared with each other and each
  request's first-token logits with a one-shot ``Engine.prefill`` at
  ``default_matmul_precision("highest")``.
* ``--chips 4`` runs only this phase: phi3-medium-14b at published
  widths cut to its first 8 layers, bf16, posit16 KV, under
  ``--model-parallel 4``, compared with the same trace on one chip.

Compile seconds, steady tokens/s and peak device memory are printed
for the record; nothing is claimed from them.  Every check that fails
is listed on stderr and the script exits 1.  With no TPU (for example
``JAX_PLATFORMS=cpu``) it exits 2; it never falls back to the CPU.  The
last line of standard output, printed only when every phase passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import softposit_ref as sp  # noqa: E402
from repro.core.types import POSIT8, POSIT16  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels._compat import resolve_interpret  # noqa: E402
from repro.launch import compile_cache, serve  # noqa: E402
from repro.runtime.engine import Engine  # noqa: E402

PAPER_DIV_ACC = 0.9584          # paper §VI accuracy table, nr3 div row

# First-token logits, max |a - b| / max |reference| per request.  Derived
# from the same comparisons at reduced widths in bf16 on the CPU (the
# worst of two seeds, times 4): chunked serving vs one-shot prefill
# 4.0e-2 at 62 layers (the KV of earlier chunks is read back through the
# posit16 codec, then bf16); model-parallel 4 vs one device 2.3e-2 at 8
# layers (bf16 partial sums meet in the all-reduce).
LOGITS_RTOL = 0.16
TP_LOGITS_RTOL = 0.1

SERVE = dict(arch="minicpm3-4b", layers=0, reduced=False, slots=8,
             n_requests=16, prompt=(512, 2048), gen=(64, 128),
             max_len=4096, block=16, chunk=64)
TP_SERVE = dict(arch="phi3-medium-14b", layers=8, reduced=False, slots=8,
                n_requests=8, prompt=(512, 1024), gen=(32, 64),
                max_len=2048, block=16, chunk=32)

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


@contextlib.contextmanager
def compile_seconds():
    """Seconds spent tracing, lowering and compiling inside the block."""
    box = [0.0]

    def on(event, secs, **_):
        if event in _COMPILE_EVENTS:
            box[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


@contextlib.contextmanager
def cache_hits():
    """Persistent compilation cache hits inside the block."""
    box = [0]

    def on(event, **_):
        if event == _CACHE_HIT:
            box[0] += 1

    jax.monitoring.register_event_listener(on)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_listener(on)


def run_native(fn, *args):
    """Compile ``fn`` for ``args`` and run it; also report whether the
    program holds its Pallas kernel.  Kernels must be native wherever
    the backend resolves them so (everywhere but the CPU)."""
    compiled = jax.jit(fn).lower(*args).compile()
    kernel_ok = resolve_interpret() or \
        "tpu_custom_call" in compiled.as_text()
    return np.asarray(compiled(*args)), kernel_ok


def _dot1(a, b, cfg):
    return sp.dot([a], [b], cfg)


_P8_OPS = (
    ("add", lambda a, b: ops.vadd(a, b, POSIT8), sp.add),
    ("sub", lambda a, b: ops.vsub(a, b, POSIT8), sp.sub),
    ("mul", lambda a, b: ops.vmul(a, b, POSIT8), sp.mul),
    ("div_exact", lambda a, b: ops.vdiv(a, b, POSIT8, mode="exact"),
     sp.div),
    ("div_nr3", lambda a, b: ops.vdiv(a, b, POSIT8, mode="nr3"), sp.div),
    # a length-1 quire reduction is an exactly rounded multiply through
    # the whole dot datapath
    ("dot", lambda a, b: ops.dot(a[:, None], b[:, None], POSIT8), _dot1),
)


def phase_posit8(fail):
    """Exhaustive posit8: every op over all 256 x 256 pattern pairs."""
    pats = np.arange(256, dtype=np.uint8)
    a, b = (x.reshape(-1) for x in np.meshgrid(pats, pats, indexing="ij"))
    ja, jb = jnp.asarray(a), jnp.asarray(b)
    print("posit8 exhaustive (65536 pairs) vs SoftPosit semantics:")
    print("  op,accuracy,required,native_kernel")
    for name, fn, gold in _P8_OPS:
        got, kernel_ok = run_native(fn, ja, jb)
        want = np.array([gold(int(x), int(y), POSIT8)
                         for x, y in zip(a, b)], np.uint8)
        acc = float((got.astype(np.uint8) == want).mean())
        need = PAPER_DIV_ACC if name == "div_nr3" else 1.0
        print(f"  {name},{acc:.6f},{need:.4f},{kernel_ok}")
        if acc < need:
            fail(f"posit8 {name}: accuracy {acc:.6f} < {need}")
        if not kernel_ok:
            fail(f"posit8 {name}: no Pallas kernel in the compiled program")


def phase_posit16(fail, n_pairs: int, seed: int):
    """Random posit16 patterns (NaR and zero included), every kernel bit-
    compared with the pure-jnp reference on the same device."""
    rng = np.random.default_rng(seed)

    def pats(*shape):
        return jnp.asarray(rng.integers(0, 1 << 16, size=shape,
                                        dtype=np.uint16))

    a, b = pats(n_pairs // 1024, 1024), pats(n_pairs // 1024, 1024)
    rows = max(1, n_pairs // 4096)
    da, db = pats(rows, 4096), pats(rows, 4096)
    # two K tiles (4096 + a ragged 1000): the streamed quire combine
    sa, sb = pats(max(1, rows // 8), 5096), pats(max(1, rows // 8), 5096)
    ga, gw = pats(24, 600), pats(600, 40)
    cases = []
    for name, fn, op, mode in (
            ("add", ops.vadd, "add", "nr3"), ("sub", ops.vsub, "sub", "nr3"),
            ("mul", ops.vmul, "mul", "nr3"),
            ("div_nr3", functools.partial(ops.vdiv, mode="nr3"), "div",
             "nr3"),
            ("div_exact", functools.partial(ops.vdiv, mode="exact"), "div",
             "exact")):
        cases.append((
            name, lambda x, y, fn=fn: fn(x, y, POSIT16),
            lambda x, y, op=op, mode=mode: ref.elementwise_ref(
                x, y, POSIT16, op, div_mode=mode), (a, b)))
    cases += [
        ("dot", lambda x, y: ops.dot(x, y, POSIT16),
         lambda x, y: ref.vpdot_rows_ref(x, y, POSIT16), (da, db)),
        ("dot_2tiles", lambda x, y: ops.dot(x, y, POSIT16),
         lambda x, y: ref.vpdot_rows_ref(x, y, POSIT16), (sa, sb)),
        ("pgemm", lambda x, y: ops.pgemm(x, y, POSIT16),
         lambda x, y: ref.pgemm_ref(x, y, POSIT16), (ga, gw)),
    ]
    print(f"posit16 random patterns vs kernels/ref.py on "
          f"{jax.devices()[0].device_kind}:")
    print("  op,shape,mismatches,native_kernel")
    for name, fn, ref_fn, args in cases:
        got, kernel_ok = run_native(fn, *args)
        want = np.asarray(jax.jit(ref_fn)(*args))
        bad = int((got != want).sum())
        shape = "x".join(str(d) for d in args[0].shape)
        print(f"  {name},{shape},{bad},{kernel_ok}")
        if bad:
            fail(f"posit16 {name}: {bad} outputs differ from kernels/ref.py")
        if not kernel_ok:
            fail(f"posit16 {name}: no Pallas kernel in the compiled program")


def serve_argv(spec, kernel: str, seed: int, model_parallel: int = 1):
    """``launch/serve.py`` arguments for one continuous chunked run."""
    argv = ["--arch", spec["arch"], "--continuous", "--kv-posit", "posit16",
            "--decode-kernel", kernel, "--seed", str(seed),
            "--batch", str(spec["slots"]),
            "--n-requests", str(spec["n_requests"]),
            "--min-prompt-len", str(spec["prompt"][0]),
            "--prompt-len", str(spec["prompt"][1]),
            "--min-gen", str(spec["gen"][0]), "--gen", str(spec["gen"][1]),
            "--max-len", str(spec["max_len"]),
            "--block-size", str(spec["block"]),
            "--chunk-size", str(spec["chunk"]),
            # every request is queued within the first round
            "--arrival-rate", "4",
            "--model-parallel", str(model_parallel)]
    if spec["layers"]:
        argv += ["--layers", str(spec["layers"])]
    if spec["reduced"]:
        argv.append("--reduced")
    return argv


def serve_run(label: str, argv):
    """One ``serve.main`` run; returns (completions in trace order, the
    scheduler).  Prints compile seconds and steady tokens/s."""
    with compile_seconds() as comp:
        t0 = time.perf_counter()
        done, sched = serve.main(argv)
        wall = time.perf_counter() - t0
    tokens = sum(len(c.tokens) for c in done.values())
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    steady = tokens / max(wall - comp[0], 1e-9)
    print(f"[{label}] {len(done)} requests, {tokens} tokens; wall "
          f"{wall:.1f} s, of which compile {comp[0]:.1f} s; steady "
          f"{steady:.1f} tok/s on {len(jax.devices())} x "
          f"{dev.device_kind}; peak_bytes_in_use "
          f"{peak if peak is not None else 'not reported'}")
    return [done[r] for r in sorted(done)], sched


def trace_prompts(spec, vocab: int, seed: int):
    """The prompts ``serve.main`` drew for ``spec`` (same rng, same
    order as the request ids)."""
    trace = serve.poisson_trace(
        np.random.default_rng(seed), spec["n_requests"], 4.0, vocab,
        spec["prompt"][1], spec["gen"][1],
        min_prompt_len=spec["prompt"][0], min_gen=spec["gen"][0])
    return [p for _, p, _ in trace], [g for _, _, g in trace]


def logits_err(got, want) -> float:
    """max |got - want| / max |want|."""
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def compare_runs(fail, label, runs_a, runs_b, tol: float):
    """Two runs of one trace: first-token logits within ``tol``, first
    tokens equal unless the reference's top-2 gap is inside the measured
    logit difference (a tie that noise may break).  Decode streams are
    reported, not gated: greedy decoding turns any rounding difference
    between two correct paths into another token at the first near-tie,
    and the streams share nothing after it."""
    same, firsts, worst = 0, 0, 0.0
    for i, (ca, cb) in enumerate(zip(runs_a, runs_b)):
        err = logits_err(ca.first_logits, cb.first_logits)
        worst = max(worst, err)
        if err > tol:
            fail(f"{label}: request {i} first-token logits differ by "
                 f"{err:.3e} > {tol:.1e}")
        top2 = np.sort(cb.first_logits)[-2:]
        tie = top2[1] - top2[0] <= np.abs(
            ca.first_logits - cb.first_logits).max()
        if ca.tokens[0] == cb.tokens[0]:
            firsts += 1
        elif not tie:
            fail(f"{label}: request {i} first token {ca.tokens[0]} vs "
                 f"{cb.tokens[0]} with a clear top-2 gap")
        if np.array_equal(ca.tokens, cb.tokens):
            same += 1
        else:
            n = min(len(ca.tokens), len(cb.tokens))
            div = int(np.argmax(ca.tokens[:n] != cb.tokens[:n])) \
                if (ca.tokens[:n] != cb.tokens[:n]).any() else n
            print(f"  [{label}] request {i}: streams diverge at token "
                  f"{div} of {n}")
    print(f"[{label}] identical streams {same}/{len(runs_a)}, identical "
          f"first tokens {firsts}/{len(runs_a)}, first-token logits max "
          f"rel diff {worst:.3e} (tolerance {tol:.1e})")


def check_lengths(fail, label, runs, gens):
    for i, (c, g) in enumerate(zip(runs, gens)):
        if len(c.tokens) != g:
            fail(f"{label}: request {i} produced {len(c.tokens)} tokens, "
                 f"asked for {g}")


def second_compile(sched):
    """Drop the in-process caches and run one idle serving round from
    outside the serving loop: the step must load from the persistent
    cache although its call path differs (``compile_cache.enable``).
    Returns (persistent cache hit, Pallas kernel in the step program)."""
    eng = sched.engine
    b, c = sched.n_slots, sched.chunk_size
    zi = np.zeros((b,), np.int32)
    jax.clear_caches()
    with cache_hits() as hits:
        eng.mixed_step(sched.cache, np.zeros((b, c), np.int32), zi, zi, c,
                       decode_active=np.zeros((b,), bool))
    text = eng._decode_jit[("mixed", c, c)].lower(
        eng.params, sched.cache, jnp.zeros((b, c), jnp.int32), zi, zi,
        eng._key, jnp.zeros((b,), bool),
        sched.cache["block_tables"]).as_text()
    return hits[0] > 0, "tpu_custom_call" in text


def check_decode_kernel(fail, sched, seed: int):
    """The fused paged-decode kernel against the gather path on the arena
    the serving run left behind (layer 0): random queries, every row
    walking its own arena blocks up to a random length.  The gather path
    runs at the backend's default matmul precision and at "highest"; the
    kernel must be no farther from the "highest" result than twice the
    default-precision gather is (floor 1e-6).  A wrong block, mask or
    decode is off by O(1); matmul precision by O(bf16)."""
    from repro.models import layers as L
    from repro.models import transformer as T
    eng, cache = sched.engine, sched.cache
    cfg = eng.cfg
    rng = np.random.default_rng(seed)
    b, w, bs = sched.n_slots, sched.table_width, sched.block_size
    tables = jnp.asarray(rng.permutation(sched.n_blocks)[:b * w]
                         .reshape(b, w).astype(np.int32))
    lens = jnp.asarray(rng.integers(1, w * bs, size=b).astype(np.int32))

    def q(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    if cfg.mla:
        args = (q(b, cfg.n_heads, cfg.kv_lora_rank),
                q(b, cfg.n_heads, cfg.qk_rope_dim), cache["c_kv"][0],
                cache["k_rope"][0], tables, lens)

        def attend(kernel):
            return jax.jit(lambda *a: L.decode_attention_paged_mla(
                *a, cfg=cfg, kv_posit=cfg.kv_posit, kernel=kernel))(*args)
    else:
        args = (q(b, 1, cfg.n_heads, cfg.head_dim), cache["k"][0],
                cache["v"][0], tables, lens)

        def attend(kernel):
            return jax.jit(lambda *a: L.decode_attention_paged(
                *a, cfg=cfg, kv_posit=cfg.kv_posit,
                window=T._paged_window(cfg), kernel=kernel))(*args)

    fused = np.asarray(attend("fused"))
    gather = np.asarray(attend("gather"))
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(attend("gather"))
    e_fused, e_gather = logits_err(fused, exact), logits_err(gather, exact)
    print(f"[decode kernel, layer 0 arena] max rel diff vs gather at "
          f"'highest': fused {e_fused:.3e}, gather at default precision "
          f"{e_gather:.3e}")
    if e_fused > 2 * max(e_gather, 1e-6):
        fail(f"fused decode kernel off the gather path by {e_fused:.3e}, "
             f"more than twice the default-precision gap {e_gather:.3e}")


def phase_serving(fail, spec, seed: int, tol: float = LOGITS_RTOL):
    """Fused and gather serving runs of one trace, cross-checked and
    checked against a one-shot reference prefill."""
    runs = {}
    for kernel in ("fused", "gather"):
        done, sched = serve_run(f"serve {spec['arch']} {kernel}",
                                serve_argv(spec, kernel, seed))
        runs[kernel] = done
        if kernel == "fused":
            hit, has_kernel = second_compile(sched)
            print(f"[serve fused] second compile of mixed_step: "
                  f"persistent cache {'hit' if hit else 'miss'}; "
                  f"tpu_custom_call present: {has_kernel}")
            if not (has_kernel or resolve_interpret()):
                fail("fused serving step holds no Pallas kernel")
            check_decode_kernel(fail, sched, seed)
            del sched
            gc.collect()
    cfg, params = sched.engine.cfg, sched.engine.params
    prompts, gens = trace_prompts(spec, cfg.vocab, seed)
    del sched
    gc.collect()
    for kernel, done in runs.items():
        check_lengths(fail, f"serve {kernel}", done, gens)
    compare_runs(fail, "fused vs gather", runs["fused"], runs["gather"],
                 tol)

    ref_eng = Engine(cfg, params, max_len=max(len(p) for p in prompts))
    with jax.default_matmul_precision("highest"):
        _, ref_logits, _ = ref_eng.prefill(prompts)
    ref_logits = np.asarray(ref_logits)
    for kernel, done in runs.items():
        errs = [logits_err(c.first_logits, ref_logits[i])
                for i, c in enumerate(done)]
        print(f"[serve {kernel} vs one-shot prefill] first-token logits "
              f"max rel diff {max(errs):.3e} (tolerance {tol:.1e})")
        for i, e in enumerate(errs):
            if e > tol:
                fail(f"serve {kernel}: request {i} first-token logits off "
                     f"the reference by {e:.3e} > {tol:.1e}")


def arena_placement(sched):
    """Where the paged arena lives: (spec, per-device bytes by device)."""
    leaf = sched.cache["c_kv" if sched.engine.cfg.mla else "k"]
    per_dev = {}
    for shard in leaf.addressable_shards:
        per_dev[str(shard.device)] = per_dev.get(str(shard.device), 0) + \
            shard.data.nbytes
    return leaf.sharding.spec, leaf.nbytes, per_dev


def phase_tp(fail, spec, seed: int, mp: int = 4,
             tol: float = TP_LOGITS_RTOL):
    """Tensor-parallel serving against the same trace on one chip."""
    runs = {}
    for degree in (mp, 1):
        done, sched = serve_run(
            f"serve {spec['arch']} mp={degree}",
            serve_argv(spec, "gather", seed, model_parallel=degree))
        runs[degree] = done
        if degree == mp:
            pspec, total, per_dev = arena_placement(sched)
            sharded = any(e is not None for e in pspec)
            print(f"[mp={mp}] arena spec {pspec}: "
                  f"{'head-sharded' if sharded else 'replicated'}; "
                  f"{total} bytes; per device {per_dev}")
            want = total // mp if sharded else total
            if len(per_dev) != mp or any(v != want for v in
                                         per_dev.values()):
                fail(f"mp={mp}: arena placement {per_dev} is neither "
                     f"{mp} equal head shards nor {mp} full replicas")
        cfg = sched.engine.cfg
        del sched
        gc.collect()
    _, gens = trace_prompts(spec, cfg.vocab, seed)
    for degree, done in runs.items():
        check_lengths(fail, f"mp={degree}", done, gens)
    compare_runs(fail, f"mp={mp} vs one chip", runs[mp], runs[1], tol)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel serving phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"compile cache: {compile_cache.enable()}")
    print(f"devices: {len(devices)} x {devices[0].device_kind}")

    failures = []
    if args.chips == 4:
        phase_tp(failures.append, TP_SERVE, args.seed)
    else:
        phase_posit8(failures.append)
        phase_posit16(failures.append, 1 << 20, args.seed)
        phase_serving(failures.append, SERVE, args.seed)
    if failures:
        for f in failures:
            print(f"chip_smoke FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
