"""The main path's Pallas kernels, compiled natively for a TPU v5e.

The TPU compiler is installed even where no chip is attached: a
described ``v5e:2x2`` topology compiles each kernel at real widths with
``interpret=False``, so a block shape that breaks the tiling rule, an op
Mosaic cannot lower, or a tile that overflows VMEM fails here instead of
on the chip.  The topology is described inside a fixture (never at
import): only the worker that runs this file loads the TPU library.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.types import POSIT16
from repro.kernels import ops
from repro.kernels import posit_paged_attn as ppa


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
