"""Stable names for the engine's compiled programs and their phases.

A profile names a program's module ``jit_<function>``: the serving
round (``Engine.mixed_step``) is the one module named ``jit_run``, so a
reader that times ``jit_run`` times the round whatever else the process
compiles.  Inside it, ``jax.named_scope`` tags the phases (prefill
chunk, decode attention, posit KV decode, MLP, LM head, sampling) in
each op's metadata; that must leave the compiled program as it was.
"""
import contextlib
import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import get_family
from repro.models import transformer as T
from repro.runtime.engine import Engine

SCOPES = ("prefill_chunk", "decode_attn", "kv_decode", "mlp", "lm_head",
          "sample")


def _engine(lane):
    arch = "minicpm3-4b" if lane == "mla" else "phi3-medium-14b"
    cfg = dataclasses.replace(
        configs.get_config(arch).reduced(compute_dtype="float32"),
        kv_posit="posit16")
    params = get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_len=32, paged=True, block_size=4)
    pool = T.init_paged_cache(cfg, 2, 32, 4, 2 * eng.table_width)
    return eng, pool


def _lower_mixed(eng, pool):
    i32 = jnp.zeros((2,), jnp.int32)
    return eng._mixed_fn(2).lower(
        eng.params, pool, jnp.zeros((2, 4), jnp.int32), i32, i32, eng._key,
        jnp.zeros((2,), bool), pool["block_tables"])


def _module(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


def test_mixed_step_alone_compiles_as_jit_run():
    eng, pool = _engine("mla")
    p, key = eng.params, eng._key
    tokens, lens = jnp.zeros((1, 4), jnp.int32), jnp.full((1,), 4, jnp.int32)
    prefill = eng._prefill_fn(False, ())
    row_cache, logits = jax.eval_shape(prefill, p, tokens, lens)
    i32 = jnp.zeros((2,), jnp.int32)
    lowered = {
        "prefill": prefill.lower(p, tokens, lens),
        "decode": eng._decode_fn(3).lower(p, row_cache, logits, key),
        "mixed_step": _lower_mixed(eng, pool),
    }
    eng.copy_blocks(pool, [0], [1])
    eng.poison_blocks(pool, [2])
    ids = jnp.asarray([0], jnp.int32)
    lowered["copy_blocks"] = eng._decode_jit[("copy", 1)].lower(
        pool, ids, ids)
    lowered["poison_blocks"] = eng._decode_jit[("poison", 1)].lower(
        pool, ids)
    names = {k: _module(v) for k, v in lowered.items()}
    assert names["mixed_step"] == "jit_run"
    assert len(set(names.values())) == len(names), names


def _canonical_hlo(text: str) -> str:
    """Optimized HLO without what only names or locates: op metadata,
    the source-location tables, and instruction names (numbered in
    creation order, which the scopes shift)."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(\d+ [^\n]*\n?)*", "\n", text)
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


@pytest.mark.parametrize("lane", ["mla", "dense"])
def test_named_scopes_tag_mixed_step_and_change_no_op(lane, monkeypatch):
    eng, pool = _engine(lane)
    scoped = _lower_mixed(eng, pool).compile().as_text()
    for scope in SCOPES:
        assert re.search(r'op_name="[^"]*/%s/' % scope, scoped), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lower_mixed(eng, pool).compile().as_text()
    assert not re.search(r'op_name="[^"]*/kv_decode/', bare)
    assert _canonical_hlo(scoped) == _canonical_hlo(bare)
