"""Tensor-parallel serving on a real (8 fake-device) host mesh, via
subprocess so the forced-device env var never leaks into other tests.

These tests *execute* the sharded serving stack (not just compile):
the mesh engine — weights by the ``runtime/sharding.py`` rule table,
the paged KV arena head-sharded over 'model' — must reproduce the
single-device scheduler token for token AND step for step, including
prefix-cache hits, a deadline-driven preemption restart, and the fused
Pallas decode kernel, with the arena sanitizer armed and leak-free
throughout.  The byte ledger also checks the point of the exercise:
each device holds ~1/mp of the arena content.
"""
import json
import os
import subprocess
import sys

import pytest

# 8-fake-device subprocess runs (compile-heavy): full lane only
pytestmark = pytest.mark.slow

_SCRIPT_IDENTITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import numpy as np
import jax

from repro import configs
from repro.compress.kvcache import cache_report
from repro.launch.mesh import make_host_mesh
from repro.models import get_family
from repro.runtime import spans
from repro.runtime.engine import Engine
from repro.runtime.scheduler import Scheduler

cfg = configs.get_config("phi3-medium-14b").reduced(
    compute_dtype="float32")
cfg = dataclasses.replace(cfg, n_heads=4, n_kv_heads=4)
params = get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)

rng = np.random.default_rng(0)
prompts = [list(map(int, rng.integers(1, cfg.vocab, size=n)))
           for n in (12, 9, 17, 5, 14, 11)]
prompts[3] = prompts[2][:12] + prompts[3]     # shared prefix pair

def run(mesh):
    eng = Engine(cfg, params, max_len=96, paged=True, block_size=8,
                 n_blocks=40, sanitize=True, mesh=mesh)
    sched = Scheduler(eng, n_slots=3, chunk_size=4, prefix_cache=True)
    for p in prompts:
        sched.submit(p, 12)
    out = sched.run(max_rounds=500)
    toks = {str(r): out[r].tokens.tolist() for r in sorted(out)}
    fin = {str(r): out[r].finished_step for r in sorted(out)}
    return toks, fin, sched

toks1, fin1, s1 = run(None)
toks2, fin2, s2 = run(make_host_mesh(4))
rep1, rep2 = cache_report(s1.cache), cache_report(s2.cache)
print(json.dumps({
    "tokens_match": toks1 == toks2,
    "schedule_match": fin1 == fin2,
    "prefix_hits_single": s1.stats["prefix_hits"],
    "prefix_hits_sharded": s2.stats["prefix_hits"],
    "n_leaked": s2.stats["n_leaked"],
    "arena_spec": str(s2.cache["k"].sharding.spec),
    "bytes": rep2["bytes"],
    "per_device_single": rep1["per_device_bytes"],
    "per_device_sharded": rep2["per_device_bytes"],
    "wall_p50_ms": s2.stats["step_wall_p50_ms"],
    "wall_p99_ms": s2.stats["step_wall_p99_ms"],
    "layer_slices": sum(sp.counters.get("kv_layer_slices", 0)
                        for sp in spans.snapshot()
                        if sp.name == "sched.round"
                        and sp.ids.get("sched") == s2.sched_id),
}))
"""

_SCRIPT_PREEMPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, warnings
import numpy as np
import jax

from repro import configs
from repro.launch.mesh import make_host_mesh
from repro.models import get_family
from repro.runtime.engine import Engine
from repro.runtime.scheduler import Scheduler

# satellite: a non-dividing tensor-parallel degree rounds down + warns
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    m3 = make_host_mesh(3)
mesh3_ok = (dict(m3.shape) == {"data": 4, "model": 2}
            and any("rounding down" in str(x.message) for x in w))

cfg = configs.get_config("phi3-medium-14b").reduced(
    compute_dtype="float32")
cfg = dataclasses.replace(cfg, n_heads=4, n_kv_heads=4)
params = get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(1)
prompts = [list(map(int, rng.integers(1, cfg.vocab, size=n)))
           for n in (10, 8, 12)]

def run(mesh, kernel=None):
    # pool sized so the deadline head cannot be admitted without
    # preempting a resident best-effort row
    eng = Engine(cfg, params, max_len=64, paged=True, block_size=8,
                 n_blocks=10, sanitize=True, mesh=mesh,
                 decode_kernel=kernel)
    sched = Scheduler(eng, n_slots=3, chunk_size=4)
    sched.submit(prompts[0], 16)              # best-effort, long
    sched.submit(prompts[1], 16)              # best-effort, long
    for _ in range(2):
        sched.step()
    sched.submit(prompts[2], 8, deadline=20)  # EDF head, pool is full
    out = sched.run(max_rounds=500)
    toks = {str(r): out[r].tokens.tolist() for r in sorted(out)}
    fin = {str(r): out[r].finished_step for r in sorted(out)}
    return toks, fin, sched

t1, f1, s1 = run(None)
t2, f2, s2 = run(make_host_mesh(4))
t3, f3, s3 = run(make_host_mesh(4), kernel="fused")
print(json.dumps({
    "mesh3_ok": mesh3_ok,
    "n_preempted_single": s1.n_preempted,
    "n_preempted_sharded": s2.n_preempted,
    "tokens_match": t1 == t2,
    "schedule_match": f1 == f2,
    "fused_tokens_match": t1 == t3,
    "fused_schedule_match": f1 == f3,
    "n_leaked": s2.stats["n_leaked"] + s3.stats["n_leaked"],
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


def test_sharded_serving_matches_single_device():
    r = _run(_SCRIPT_IDENTITY)
    assert r["tokens_match"], r
    assert r["schedule_match"], r
    # prefix dedup must survive sharding, hit for hit
    assert r["prefix_hits_sharded"] == r["prefix_hits_single"] > 0, r
    assert r["n_leaked"] == 0, r
    # the arena is head-sharded, and each device holds ~1/4 of it
    assert "model" in r["arena_spec"], r
    assert r["per_device_single"] == r["bytes"], r
    assert r["per_device_sharded"] < r["bytes"] / 2, r
    assert r["wall_p99_ms"] >= r["wall_p50_ms"] > 0, r


def test_sharded_preemption_and_fused_kernel_match():
    r = _run(_SCRIPT_PREEMPT)
    assert r["mesh3_ok"], r
    # the deadline request forces a restart in BOTH runs, identically
    assert r["n_preempted_single"] > 0, r
    assert r["n_preempted_sharded"] == r["n_preempted_single"], r
    assert r["tokens_match"] and r["schedule_match"], r
    assert r["fused_tokens_match"] and r["fused_schedule_match"], r
    assert r["n_leaked"] == 0, r


def test_sharded_serving_reads_the_stacked_arena_flat():
    """The same at KV heads of 128, where the decode gather reads the
    head-sharded stacked arena flattened to ``(L*n_blocks, ...)``
    (``layers.paged_reads_flat``) and slices no layer: still token and
    step identical to one device, with the arena head-sharded."""
    r = _run(_SCRIPT_IDENTITY.replace(
        "n_kv_heads=4)", "n_kv_heads=4, head_dim=128)"))
    assert r["tokens_match"] and r["schedule_match"], r
    assert r["prefix_hits_sharded"] == r["prefix_hits_single"] > 0, r
    assert r["n_leaked"] == 0 and r["layer_slices"] == 0, r
    assert "model" in r["arena_spec"], r
    assert r["per_device_sharded"] < r["bytes"] / 2, r
