"""Checkpointing: atomicity, resume, keep-k GC, posit payload, elastic."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "layers": {"w": jnp.asarray(rng.standard_normal((8, 16)),
                                    jnp.float32),
                   "b": jnp.asarray(rng.standard_normal(16), jnp.float32)},
        "count": jnp.asarray(7, jnp.int32),
    }


def test_save_restore_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    t = _tree()
    ck.save(10, t, blocking=True)
    assert ck.latest_step() == 10
    restored, step = ck.restore(10, t)
    assert step == 10
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_and_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _tree(s))
    ck.wait()
    ck._gc()
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path)
                   if n.startswith("step_"))
    assert steps == [3, 4]


def test_interrupted_save_never_corrupts(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    ck.save(5, _tree(5), blocking=True)
    # simulate a crash mid-save: a stale tmp dir must be ignored
    os.makedirs(tmp_path / "tmp.6")
    with open(tmp_path / "tmp.6" / "arrays.npz", "w") as f:
        f.write("garbage")
    assert ck.latest_step() == 5
    restored, _ = ck.restore(5, _tree())
    assert np.isfinite(np.asarray(restored["layers"]["w"])).all()


@pytest.mark.slow
def test_posit_payload_roundtrip_accuracy(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=1, posit_payload=True)
    t = _tree(3)
    ck.save(1, t, blocking=True)
    restored, _ = ck.restore(1, t)
    w0 = np.asarray(t["layers"]["w"])
    w1 = np.asarray(restored["layers"]["w"])
    # posit16 has >= 9 fraction bits around |x|~1: tight but lossy
    np.testing.assert_allclose(w1, w0, rtol=3e-3, atol=1e-4)
    # int leaves stay exact
    assert int(restored["count"]) == 7


def test_elastic_remesh_restore(tmp_path):
    """Save under one layout, restore under a different mesh — the
    checkpoint is mesh-agnostic (elastic scaling)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    ck = Checkpointer(str(tmp_path), keep=1)
    t = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    ck.save(2, t, blocking=True)
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = {"w": NamedSharding(mesh, P(None, None))}
    restored, _ = ck.restore(2, t, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(t["w"]))
    assert restored["w"].sharding == sh["w"]
