"""Distributed correctness on a real (8 fake-device) mesh, via subprocess
so the 512-device dry-run env var never leaks into other tests.

These tests *execute* the sharded programs (not just compile): the sharded
train step must match the single-device step numerically, and the
compressed cross-pod path must put uint16 all-gathers on the wire.
"""
import json
import os
import subprocess
import sys

import pytest

# 8-fake-device subprocess runs (compile-heavy): full lane only
pytestmark = pytest.mark.slow

_SCRIPT_NUMERIC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.models import get_family
from repro.optim import adamw
from repro.runtime import sharding, train_loop
from repro.data.pipeline import DataConfig, Pipeline

cfg = configs.get_config("granite-moe-3b-a800m").reduced(
    compute_dtype="float32")
import dataclasses
cfg = dataclasses.replace(cfg, fsdp=False, seq_shard_activations=False)
fam = get_family(cfg)
opt_cfg = adamw.AdamWConfig(lr=1e-3)
pipe = Pipeline(DataConfig(seed=5), cfg, global_batch=8, seq_len=32)
batch = pipe.batch_at(0)

params = fam.init_params(jax.random.PRNGKey(0), cfg)
opt = adamw.init(params, opt_cfg)
step_fn = train_loop.make_train_step(cfg, opt_cfg)

# single-device reference
p1, o1, m1 = jax.jit(step_fn)(params, opt, batch, jnp.asarray(0))

# sharded 4x2 mesh
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
p_sh = sharding.param_shardings(params, mesh)
b_sh = sharding.to_shardings(sharding.batch_specs(batch, mesh, cfg), mesh)
params_s = jax.device_put(params, p_sh)
opt_s = jax.device_put(opt, sharding.param_shardings(opt, mesh))
batch_s = jax.device_put(batch, b_sh)
with jax.set_mesh(mesh):
    p2, o2, m2 = jax.jit(step_fn)(params_s, opt_s, batch_s,
                                  jnp.asarray(0))

l1, l2 = float(m1["loss"]), float(m2["loss"])
dw = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                               b.astype(jnp.float32))))
         for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
print(json.dumps({"loss1": l1, "loss2": l2, "max_param_diff": dw}))
"""

_SCRIPT_COMPRESSED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import numpy as np
import jax, jax.numpy as jnp

from repro import configs
from repro.models import get_family
from repro.optim import adamw
from repro.runtime import sharding, train_loop
from repro.compress import gradient as gc
from repro.data.pipeline import DataConfig, Pipeline
from repro.launch.hlo_analysis import collective_bytes

cfg = configs.get_config("internvl2-1b").reduced(compute_dtype="float32")
cfg = dataclasses.replace(cfg, fsdp=False, seq_shard_activations=False,
                          batch_axes=("pod", "data"),
                          grad_compress="posit16", n_visual_tokens=0)
fam = get_family(cfg)
opt_cfg = adamw.AdamWConfig(lr=1e-3)
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)

params = fam.init_params(jax.random.PRNGKey(0), cfg)
opt = adamw.init(params, opt_cfg)
ef = jax.tree.map(lambda p: jnp.zeros((2,) + p.shape, jnp.float32), params)
pipe = Pipeline(DataConfig(seed=9), cfg, global_batch=8, seq_len=32)
batch = pipe.batch_at(0)
tiled = jax.tree.map(lambda x: x.reshape((2, 4) + x.shape[1:]), batch)

step_fn = train_loop.make_train_step(cfg, opt_cfg, n_pods=2,
                                     compressed=True)
with jax.set_mesh(mesh):
    jitted = jax.jit(step_fn)
    lowered = jitted.lower(params, opt, ef, tiled, jnp.asarray(0))
    compiled = lowered.compile()
    colls = collective_bytes(compiled.as_text())
    has_u16_gather = "u16" in compiled.as_text() and \
        colls.get("all-gather", 0) > 0
    # Execute the AOT executable compiled above.  Re-invoking ``jitted``
    # with explicitly device_put (committed-sharding) inputs forces a
    # second lowering whose SPMD partitioning pass is pathologically slow
    # (>10 min, XLA "Very slow compile" alarm) on jax 0.4.x CPU hosts
    # with 8 forced devices; the AOT call reuses the fast first compile
    # and the in-step sharding constraints still drive the collectives.
    p2, o2, ef2, m2 = compiled(params, opt, ef, tiled, jnp.asarray(0))
print(json.dumps({
    "loss": float(m2["loss"]),
    "colls": {k: int(v) for k, v in colls.items()},
    "has_u16_gather": bool(has_u16_gather),
    "ef_nonzero": bool(any(float(jnp.abs(x).max()) > 0
                           for x in jax.tree.leaves(ef2))),
}))
"""


def _run(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    r = _run(_SCRIPT_NUMERIC)
    assert abs(r["loss1"] - r["loss2"]) < 1e-4, r
    assert r["max_param_diff"] < 1e-4, r


def test_compressed_multipod_train_wire_is_posit16():
    r = _run(_SCRIPT_COMPRESSED)
    assert r["has_u16_gather"], r      # the pod sync moves uint16 patterns
    assert r["ef_nonzero"], r          # error feedback captured residue
    assert r["loss"] > 0
