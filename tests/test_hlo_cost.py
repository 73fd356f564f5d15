"""Trip-count-aware HLO cost analyzer: validation against known kernels."""
import subprocess
import sys
import os
import json

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_cost import analyze
from repro.launch.hlo_analysis import collective_bytes

results = {}

# 1. scan of matmuls: flops must be ~ 2*M*N*K*T
def f(x, w):
    def body(c, _):
        return jnp.tanh(c @ w), None
    out, _ = jax.lax.scan(body, x, None, length=17)
    return out.sum()

comp = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32),
                        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
r = analyze(comp.as_text())
results["scan_flops"] = r["flops"]
results["scan_expected"] = 2 * 64 * 64 * 64 * 17

# 2. sharded: per-chip flops ~ global/8; collectives trip-multiplied
mesh = jax.make_mesh((8,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
ws = NamedSharding(mesh, P(None, "model"))
comp2 = jax.jit(f, in_shardings=(ws, ws)).lower(
    jax.ShapeDtypeStruct((64, 64), jnp.float32),
    jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
r2 = analyze(comp2.as_text())
results["sharded_flops"] = r2["flops"]
results["sharded_expected"] = 2 * 64 * 64 * 64 * 17 / 8
results["coll_trip"] = r2["collectives"].get("all-gather", 0)
results["coll_once"] = collective_bytes(comp2.as_text()).get("all-gather", 0)

# 3. nested scans multiply
def g(x):
    def outer(c, _):
        def inner(d, _):
            return d * 1.5 + 1.0, None
        d, _ = jax.lax.scan(inner, c, None, length=5)
        return d, None
    out, _ = jax.lax.scan(outer, x, None, length=7)
    return out.sum()

comp3 = jax.jit(g).lower(
    jax.ShapeDtypeStruct((128,), jnp.float32)).compile()
r3 = analyze(comp3.as_text())
results["nested_flops"] = r3["flops"]
results["nested_expected_min"] = 128 * 2 * 5 * 7   # mul+add per element

print(json.dumps(results))
"""


def test_trip_aware_cost_analyzer():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    # flops within 5% of the closed form (elementwise ops add a little)
    assert abs(r["scan_flops"] - r["scan_expected"]) \
        < 0.05 * r["scan_expected"], r
    assert abs(r["sharded_flops"] - r["sharded_expected"]) \
        < 0.10 * r["sharded_expected"], r
    # the collective inside the scan counts 17x the once-through number
    assert r["coll_trip"] >= 16 * r["coll_once"], r
    # nested loops multiply (7 * 5)
    assert r["nested_flops"] >= r["nested_expected_min"], r
