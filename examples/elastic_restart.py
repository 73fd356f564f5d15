"""Elastic scaling demo: train, 'lose' half the cluster, resume.

Trains a small LM on a 2-device mesh, checkpoints, then restores the
same checkpoint onto a 1-device mesh (different sharding layout) and
continues — loss continues from where it left off.  This is the
mesh-agnostic checkpoint path that lets a 512-chip job resume on 256
chips after losing a pod.

  PYTHONPATH=src python examples/elastic_restart.py
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.checkpoint.checkpointer import Checkpointer  # noqa: E402
from repro.data.pipeline import DataConfig, Pipeline  # noqa: E402
from repro.models import get_family  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.runtime import sharding, train_loop  # noqa: E402


def main():
    cfg = configs.get_config("gemma-7b").reduced(compute_dtype="float32")
    fam = get_family(cfg)
    opt_cfg = adamw.AdamWConfig(lr=1e-3)
    pipe = Pipeline(DataConfig(seed=17), cfg, global_batch=8, seq_len=64)
    step_fn = jax.jit(train_loop.make_train_step(cfg, opt_cfg))
    ckpt = Checkpointer("/tmp/repro_elastic_ckpt", keep=1)

    # ---- phase 1: 2-device mesh (data x model = 2 x 1)
    mesh2 = jax.make_mesh((2, 1), ("data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params = fam.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params, opt_cfg)
    p_sh2 = sharding.param_shardings(params, mesh2)
    params = jax.device_put(params, p_sh2)
    losses = []
    with jax.set_mesh(mesh2):
        for i in range(20):
            params, opt, m = step_fn(params, opt, pipe.batch_at(i),
                                     jnp.asarray(i, jnp.int32))
            losses.append(float(m["loss"]))
    ckpt.save(20, {"params": params, "opt": opt}, blocking=True)
    print(f"phase 1 (2 devices): loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"checkpoint at step 20")

    # ---- phase 2: 'a device died' -> resume on a 1-device mesh
    mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 2)
    template = {"params": params, "opt": opt}
    sh1 = {
        "params": sharding.param_shardings(params, mesh1),
        "opt": sharding.param_shardings(opt, mesh1),
    }
    state, step0 = ckpt.restore(20, template, shardings=sh1)
    params1, opt1 = state["params"], state["opt"]
    with jax.set_mesh(mesh1):
        resumed = []
        for i in range(step0, step0 + 10):
            params1, opt1, m = step_fn(params1, opt1, pipe.batch_at(i),
                                       jnp.asarray(i, jnp.int32))
            resumed.append(float(m["loss"]))
    print(f"phase 2 (1 device, restored step {step0}): "
          f"loss {resumed[0]:.3f} -> {resumed[-1]:.3f}")
    assert resumed[0] < losses[0], "resume lost training progress"
    print("OK: elastic re-mesh resume preserved progress")


if __name__ == "__main__":
    main()
