"""The plain reference against the program's one-shot prefill, at toy
widths on the CPU, every lane found (``lane.names()``), in float32 with
no KV codec (so the two must agree to float32 rounding)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
import reference
import weights
from conftest import TINY
from lane import names


def _f32(lane):
    c = dict(TINY[lane], torch_dtype="float32")
    cfg = dataclasses.replace(program.program_config(c), kv_posit=None)
    return c, cfg


@pytest.mark.parametrize("lane", names())
def test_layout_is_the_programs_tree(lane):
    from repro.models import get_family
    c, cfg = _f32(lane)
    ours = jax.eval_shape(lambda: weights.make(c, 0, jnp.float32))
    theirs = jax.eval_shape(
        lambda: get_family(cfg).init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == \
        [x.shape for x in jax.tree.leaves(theirs)]


@pytest.mark.parametrize("lane", names())
@pytest.mark.parametrize("n", [37, 300])
def test_reference_matches_one_shot_prefill(lane, n):
    from repro.models import transformer as T
    c, cfg = _f32(lane)
    w = weights.make(c, 3, jnp.float32)
    toks = np.random.default_rng(n).integers(1, c["vocab_size"], n)
    ref = np.asarray(reference.logits(c, w, toks.tolist()))
    _, last = T.prefill(w, jnp.asarray(toks)[None], cfg)
    full = np.asarray(T.logits_fn(w, jnp.asarray(toks)[None], cfg))[0]
    scale = np.abs(ref).max()
    assert ref.shape == (n, c["vocab_size"])
    assert np.abs(np.asarray(last)[0] - ref[-1]).max() <= 1e-4 * scale
    assert np.abs(full - ref).max() <= 1e-4 * scale


def test_fp8_control_departs_from_f32():
    c, _ = _f32("gqa")
    w = weights.make(c, 4, jnp.float32)
    toks = list(range(1, 60))
    a = np.asarray(reference.logits(c, w, toks))
    b = np.asarray(reference.logits(c, w, toks, "fp8"))
    rel = np.abs(a - b).max() / np.abs(a).max()
    assert 1e-3 < rel < 0.5
