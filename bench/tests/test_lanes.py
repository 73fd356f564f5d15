"""Model lanes (``lane.py``): every number the harness derives from a
config file is the same as before the lanes were split out, and a new
lane comes in as new files only.

The goldens were recorded on the CPU with the single-module harness
that the lanes replaced (``weights.layout``,
``reference._layer``, ``flops.per_position`` and ``hbm`` each branching
on MLA or GQA): sha256 of every leaf ``weights.make`` gives (path,
shape, dtype, bytes, in leaf order, so the same ``fold_in`` index per
leaf), of ``reference.logits`` on ``TOKENS`` (the f32 reference and the
fp8 control), and the counts of both real config files on ``COUNTERS``.
"""
import hashlib
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops
import hbm
import lane
import reference
import run
import weights
from conftest import TINY, tiny_cell

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TOKENS = [int(t) for t in np.random.default_rng(7).integers(1, 256, 300)]
COUNTERS = [
    {"decode_steps": 64, "prefill_rows": 1, "decode_rows": 15,
     "kv_live_positions": 412345},
    {"decode_steps": 64, "prefill_rows": 0, "decode_rows": 6,
     "kv_live_positions": 987654},
    {"decode_steps": 0, "prefill_rows": 2, "decode_rows": 0,
     "kv_live_positions": 0},
]

WEIGHTS = {
    ("mla", "float32", 0):
        "77cdcdf338d1ee742394db90776d3f4cad7d8d00d1c9f626f2138e852c643088",
    ("mla", "float32", 3):
        "6e3f768c0a6d1da70c4a494aedc97f4a61083cad75bef9185850b3f3b8a9751e",
    ("mla", "bfloat16", 0):
        "5e5e5621f68a4a79d69d3b517ef2b3364f77825e92da2fd1d8f1e7615a95eca1",
    ("mla", "bfloat16", 3):
        "520516d8130a7c6b827a27850ef0805b09c3a15563fa72d04b72134717631698",
    ("gqa", "float32", 0):
        "c356bfe41fd0fe3c59a19addbd8dbd59239f1950e9580559b326675e92ea7fb0",
    ("gqa", "float32", 3):
        "1825b1a7ab0f499cbf43f8ba3d3bfb6645aaed36728ace1c1d34325ab1e3f325",
    ("gqa", "bfloat16", 0):
        "ceead12e9987d529489e5d95b60331b27df21585144f48d240a5debf25efbb53",
    ("gqa", "bfloat16", 3):
        "c5959d821c031d779b012b3264391c1a14381f8275fb66737e5326dd54b998a8",
}
LOGITS = {     # on the bf16 weights of seed 3
    ("mla", "f32"):
        "adcab031e20876d0d879f37774f33db0cf425434c95cd24c6bcb46561a6e9b45",
    ("mla", "fp8"):
        "465ce1f2b96153ed42803de74cd50833e21f582c31daca83cfcc008ec2d0770d",
    ("gqa", "f32"):
        "481876b8bd4636fc3a2a826e4c0ae38a3db23a062145d6781f0cb2d5ff200dec",
    ("gqa", "fp8"):
        "2bad2b607394dec2b8a75367d2866a1082b09237cfeb0f6883634322a7f4ac08",
}
COUNTS = {
    "minicpm3-4b": dict(
        per_position={"dense": 7770931200, "attn_per_ctx": 793600,
                      "head": 376053760},
        weight_bytes=8147751936, kv_bytes_per_position=35712,
        round_bytes=[544363824000, 556740936960, 8147751936]),
    "phi3-medium-14b.pp4": dict(
        per_position={"dense": 6815744000, "attn_per_ctx": 204800,
                      "head": 328335360},
        weight_bytes=7144294400, kv_bytes_per_position=51200,
        round_bytes=[485540352000, 507822387200, 7144294400]),
}


def _tree_sha(w) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}{a.shape}{a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _array_sha(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(f"{a.shape}{a.dtype}".encode()
                          + a.tobytes()).hexdigest()


@pytest.mark.parametrize("name,dtype,seed", sorted(WEIGHTS))
def test_weights_are_the_parents(name, dtype, seed):
    w = weights.make(TINY[name], seed, getattr(jnp, dtype))
    assert _tree_sha(w) == WEIGHTS[name, dtype, seed]


@pytest.mark.parametrize("name,precision", sorted(LOGITS))
def test_reference_logits_are_the_parents(name, precision):
    c = TINY[name]
    out = reference.logits(c, weights.make(c, 3), TOKENS, precision)
    assert _array_sha(out) == LOGITS[name, precision]


@pytest.mark.parametrize("config", sorted(COUNTS))
def test_counts_are_the_parents(config):
    c = json.loads((CONFIGS / f"{config}.json").read_text())
    want = COUNTS[config]
    assert flops.per_position(c) == want["per_position"]
    assert hbm.weight_bytes(c) == want["weight_bytes"]
    assert hbm.kv_bytes_per_position(c) == want["kv_bytes_per_position"]
    assert [hbm.round_bytes(c, k) for k in COUNTERS] == want["round_bytes"]


def test_every_config_names_a_lane_found():
    """Each lane has its toy config (``tiny/<lane>.json``), and every
    config file names a lane that is there."""
    assert lane.names() == sorted(TINY)
    assert all(c["lane"] == name for name, c in TINY.items())
    for f in CONFIGS.glob("*.json"):
        assert json.loads(f.read_text())["lane"] in lane.names()


@pytest.mark.parametrize("given", [{}, {"lane": "no-such-lane"}])
def test_a_lane_is_never_a_default(given):
    c = {k: v for k, v in TINY["gqa"].items() if k != "lane"}
    with pytest.raises((KeyError, ValueError), match="lane"):
        weights.layout(dict(c, **given))


def test_a_new_lane_is_new_files_only(tmp_path, monkeypatch):
    """A copy of ``gqa.py`` under a new name, in a directory of its own,
    serves a whole tiny run through the harness with no other file
    touched, and computes the gqa lane's reference bit for bit."""
    base = TINY["gqa"]
    w = weights.make(base, 3)
    want = np.asarray(reference.logits(base, w, TOKENS))
    shutil.copy(lane.LANES / "gqa.py", tmp_path / "gqa_copy.py")
    monkeypatch.setattr(lane, "LANES", tmp_path)
    assert lane.names() == ["gqa_copy"]
    c = dict(base, name="tiny-gqa-copy", lane="gqa_copy")
    assert _tree_sha(weights.make(c, 3)) == WEIGHTS["gqa", "bfloat16", 3]
    assert np.array_equal(np.asarray(reference.logits(c, w, TOKENS)), want)
    result, _, _, _ = run.run_cell(
        dict(tiny_cell("gqa", max_logit_gap=0.06), config=c), 3, 6, False,
        require_chip=False)
    assert result["correct"]
    assert result["readings"]["tokens_compared"] >= 8
