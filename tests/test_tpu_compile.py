"""The main path's Pallas kernels, compiled natively for a TPU v5e.

The TPU compiler is installed even where no chip is attached: a
described ``v5e:2x2`` topology compiles each kernel at real widths with
``interpret=False``, so a block shape that breaks the tiling rule, an op
Mosaic cannot lower, or a tile that overflows VMEM fails here instead of
on the chip.  The topology is described inside a fixture (never at
import): only the worker that runs this file loads the TPU library.
"""
import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.types import POSIT16
from repro.kernels import ops
from repro.kernels import posit_paged_attn as ppa
from repro.models import get_family
from repro.models import transformer as T
from repro.runtime.engine import Engine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # described-device executables cannot be read back from the
    # persistent cache; keep it off so nothing is written or warned
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _native(name):
    """(callable, argument shapes) at the widths the system runs."""
    u16, f32, i32 = jnp.uint16, jnp.float32, jnp.int32
    if name == "paged_dense":   # phi3-medium-14b: 10 KV heads, D 128
        b, g, r, d, bs, w, nb = 8, 10, 4, 128, 16, 256, 2048
        return (lambda q, k, v, t, a, ln: ppa.paged_decode_attention(
            q, k, v, t, a, ln, pcfg=POSIT16, interpret=False),
            [((b, g, r, d), f32), ((nb, bs, g, d), u16),
             ((nb, bs, g, d), u16), ((b, w), i32), ((b, w * bs), i32),
             ((b,), i32)])
    if name == "paged_mla":     # minicpm3-4b: 40 heads, rank 256, rope 32
        b, h, rank, rope, bs, w, nb = 8, 40, 256, 32, 16, 256, 2048
        return (lambda qc, qr, c, rr, t, a, ln:
                ppa.paged_decode_attention_mla(
                    qc, qr, c, rr, t, a, ln, pcfg=POSIT16, scale=96 ** -0.5,
                    interpret=False),
                [((b, h, rank), f32), ((b, h, rope), f32),
                 ((nb, bs, rank), u16), ((nb, bs, rope), u16),
                 ((b, w), i32), ((b, w * bs), i32), ((b,), i32)])
    if name == "vadd":
        return (lambda x, y: ops.vadd(x, y, POSIT16, interpret=False),
                [((1024, 1024), u16)] * 2)
    if name == "vdiv":
        return (lambda x, y: ops.vdiv(x, y, POSIT16, interpret=False),
                [((1024, 1024), u16)] * 2)
    if name == "quantize":
        return (lambda x: ops.quantize(x, POSIT16, interpret=False),
                [((1024, 1024), f32)])
    if name == "dot":           # two K tiles: the streamed quire combine
        return (lambda x, y: ops.dot(x, y, POSIT16, interpret=False),
                [((256, 8192), u16)] * 2)
    if name == "pgemm":
        return (lambda x, y: ops.pgemm(x, y, POSIT16, interpret=False),
                [((64, 4500), u16), ((4500, 100), u16)])
    raise ValueError(name)


@pytest.mark.parametrize("name", ["paged_dense", "paged_mla", "vadd", "vdiv",
                                  "quantize", "dot", "pgemm"])
def test_kernel_compiles_natively_for_v5e(one_chip, name):
    fn, shapes = _native(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_body_does_not_depend_on_the_call_path(one_chip, monkeypatch,
                                                      tmp_path):
    """The persistent-cache key hashes the Mosaic body, which embeds its
    lowering's source locations: once the cache is enabled, one kernel
    lowered from two call paths must lower to the same program."""
    from repro.launch import compile_cache
    fn, shapes = _native("paged_mla")
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]

    def lower():
        return jax.jit(fn).lower(*args).as_text()

    def lower_elsewhere():
        return lower()

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_include_full_tracebacks_in_locations
    try:
        compile_cache.enable()
        first = lower()
        jax.clear_caches()
        second = lower_elsewhere()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
    assert "tpu_custom_call" in first
    assert first == second


def _loop_ops(hlo: str):
    """(opcode, dtype, dims, first operand's dims) of every instruction
    that a while loop's body runs, in it or in a computation it calls
    (fusions, nested loops, conditional branches), from optimized HLO
    text; size-1 dims dropped."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            cur.append(line)
    inst = re.compile(
        r"%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(%?([\w.\-]*)")
    calls = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)"
                       r"|(?<=[{ ])%([\w.\-]+)(?=[,}])")
    todo = [m.group(1) for lines in comps.values() for line in lines
            for m in re.finditer(r"body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        dims = {}
        for line in comps[name]:
            todo += [a or b for a, b in calls.findall(line)]
            op = inst.search(line)
            if op:
                dims[op.group(1)] = tuple(
                    int(d) for d in op.group(3).split(",") if d and d != "1")
                yield (op.group(4), op.group(2), dims[op.group(1)],
                       dims.get(op.group(5)))


@pytest.mark.parametrize("lane", ["mla", "dense"])
def test_paged_decode_scan_keeps_the_arena_in_place(one_chip, monkeypatch,
                                                    lane):
    """The serving round (``Engine._mixed_fn``: a prefill chunk, then a
    4-step decode scan) at the served arena (16 rows x 2048, block 16,
    posit16) and published widths, two layers.  No loop copies, slices
    or update-slices the stacked arena, nor update-slices one layer of
    it: the writes scatter into the carried arena in place.  No loop
    gathers straight off the stacked arena either: on a v5e that
    two-index gather ran up to 20x slower than slicing the layer's
    arena out (read only) and gathering from the slice.  The decode
    scan's compiled temporaries are at least one arena below the
    per-layer ``xs``/``ys`` formulation's (``paged_reference``), which
    copies the stacked arena every step and restacks every layer; the
    scan is compiled alone for that, since the round's peak is the
    prefill chunk's and would hide it."""
    from paged_reference import decode_step_xs_ys
    arch = "minicpm3-4b" if lane == "mla" else "phi3-medium-14b"
    cfg = dataclasses.replace(configs.get_config(arch), n_layers=2,
                              vocab=4096, kv_posit="posit16",
                              compute_dtype="bfloat16")
    b, max_len, bs = 16, 2048, 16
    eng = Engine(cfg, None, max_len=max_len, paged=True, block_size=bs)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: get_family(cfg).init_params(
        jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(lambda: T.init_paged_cache(
        cfg, b, max_len, bs, b * eng.table_width)))
    i32 = jax.ShapeDtypeStruct((b,), jnp.int32)
    args = on_chip((jax.ShapeDtypeStruct((b, 4), jnp.int32), i32, i32,
                    jax.ShapeDtypeStruct((2,), jnp.uint32),
                    jax.ShapeDtypeStruct((b,), jnp.bool_),
                    jax.ShapeDtypeStruct((b, eng.table_width), jnp.int32)))
    leaves = [pool[k] for k in (("c_kv", "k_rope") if cfg.mla
                                else ("k", "v"))]
    stacked = {leaf.shape for leaf in leaves}
    layer = {leaf.shape[1:] for leaf in leaves}

    def compile_scan():
        def decode_scan(params, cache, tok, active):    # traced anew
            def step(carry, _):
                cache, tok = carry
                logits, cache = T.decode_step(params, cache, tok, cfg,
                                              active=active)
                return (cache, jnp.argmax(logits, -1).astype(jnp.int32)), \
                    None

            return jax.lax.scan(step, (cache, tok), length=4)[0]

        compiled = eng._mixed_fn(4).lower(params, pool, *args).compile()
        bad = [(op, dims) for op, dt, dims, src in
               _loop_ops(compiled.as_text()) if dt == "u16" and (
                   op in ("copy", "dynamic-slice", "dynamic-update-slice")
                   and dims in stacked
                   or op == "dynamic-update-slice" and dims in layer
                   or op == "gather" and src in stacked)]
        scan = jax.jit(decode_scan).lower(
            params, pool, args[2], args[4]).compile()
        return scan.memory_analysis().temp_size_in_bytes, bad

    temp, bad = compile_scan()
    monkeypatch.setattr(T, "_decode_step_paged", decode_step_xs_ys)
    ref_temp, ref_bad = compile_scan()
    assert bad == [], bad
    assert ref_bad, "the per-layer formulation's arena moves went unseen"
    one_arena = max(leaf.size * leaf.dtype.itemsize for leaf in leaves)
    assert ref_temp - temp >= one_arena, (ref_temp, temp, one_arena)


@pytest.mark.parametrize("lane", ["dense", "mla"])
def test_paged_decode_reads_lane_dense_arenas_without_a_layer_slice(
        one_chip, monkeypatch, lane):
    """The decode step in a 4-step scan at published widths, two layers:
    phi3-medium-14b's 10 KV heads of 128 at the reason cell's arena
    (6 rows x 4096, block 16) and MiniCPM3's 256-wide latent at the chat
    cell's (16 x 2048), posit16.  No loop slices one layer out of a
    lane-dense arena: its gather reads the carried stacked arena, flat
    (``(2*n_blocks, ...)``).  The 32-wide RoPE arena keeps its slice,
    which shows the check sees one; on the dense lane, the rule forced
    to slice every arena shows it."""
    from repro.models import layers as L
    arch = "minicpm3-4b" if lane == "mla" else "phi3-medium-14b"
    cfg = dataclasses.replace(configs.get_config(arch), n_layers=2,
                              vocab=4096, kv_posit="posit16",
                              compute_dtype="bfloat16")
    b, max_len, bs = (16, 2048, 16) if lane == "mla" else (6, 4096, 16)
    eng = Engine(cfg, None, max_len=max_len, paged=True, block_size=bs)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: get_family(cfg).init_params(
        jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(lambda: T.init_paged_cache(
        cfg, b, max_len, bs, b * eng.table_width)))
    tok = on_chip(jax.ShapeDtypeStruct((b,), jnp.int32))
    active = on_chip(jax.ShapeDtypeStruct((b,), jnp.bool_))
    layer = {k: pool[k].shape[1:] for k in (("c_kv", "k_rope") if cfg.mla
                                            else ("k", "v"))}

    def reads():
        def decode_scan(params, cache, tok, active):    # traced anew
            def step(carry, _):
                cache, tok = carry
                logits, cache = T.decode_step(params, cache, tok, cfg,
                                              active=active)
                return (cache, jnp.argmax(logits, -1).astype(jnp.int32)), \
                    None

            return jax.lax.scan(step, (cache, tok), length=4)[0]

        hlo = jax.jit(decode_scan).lower(params, pool, tok,
                                         active).compile().as_text()
        ops = list(_loop_ops(hlo))
        sliced = {k for op, dt, dims, _ in ops
                  if op == "dynamic-slice" and dt == "u16"
                  for k, shape in layer.items() if dims == shape}
        flat = {k for op, dt, _, src in ops if op == "gather"
                for k, shape in layer.items()
                if src == (2 * shape[0],) + shape[1:]}
        return sliced, flat

    if lane == "mla":
        assert layer["c_kv"] == (b * eng.table_width, bs, 256)
        assert reads() == ({"k_rope"}, {"c_kv"})
    else:
        assert layer["k"] == (b * eng.table_width, bs, 10, 128)
        assert reads() == (set(), {"k", "v"})
        monkeypatch.setattr(L, "paged_reads_flat",
                            lambda minor, kernel="gather": False)
        assert reads() == ({"k", "v"}, set())
