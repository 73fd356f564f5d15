"""The chip smoke's phases, rehearsed at tiny sizes on the CPU.

``chip_smoke.py`` runs on a TPU only; here its phases run with the
Pallas kernels in interpret mode and reduced configurations, so a wrong
path, argument or comparison shows up before any chip time is spent.
The tensor-parallel phase runs in a subprocess with four forced host
devices (the same replicated-arena fallback phi3's 10 KV heads take at
``--model-parallel 4``).
"""
import json
import os
import subprocess
import sys

import pytest

import jax

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(arch="minicpm3-4b", layers=0, reduced=True, slots=2,
            n_requests=3, prompt=(6, 12), gen=(3, 5), max_len=32, block=4,
            chunk=4)


@pytest.fixture
def no_persistent_cache(monkeypatch, tmp_path):
    """``serve.main`` enables the persistent compilation cache; point the
    variable it honours elsewhere so this test process keeps none, and
    restore the location flag ``enable()`` turns off, which would
    otherwise drop the named scopes from later tests' op metadata."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    flag = "jax_include_full_tracebacks_in_locations"
    was = getattr(jax.config, flag)
    yield
    jax.config.update(flag, was)


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""                     # no result line
    assert "no TPU" in out.err


def test_posit16_phase_bit_matches_reference():
    failures = []
    chip_smoke.phase_posit16(failures.append, 1024, seed=0)
    assert failures == []


def test_serving_phase_fused_gather_and_reference_agree(
        no_persistent_cache, capsys):
    failures = []
    chip_smoke.phase_serving(failures.append, TINY, seed=0)
    out = capsys.readouterr().out
    assert failures == [], failures
    assert "identical streams 3/3" in out
    assert "second compile of mixed_step" in out
    assert "[decode kernel, layer 0 arena]" in out


def test_compare_runs_flags_a_logits_gap():
    """The comparison is not vacuous: logits off by more than the
    tolerance fail, and so does a first token flipped across a clear
    top-2 gap."""
    import numpy as np
    from repro.runtime.scheduler import Completion

    def comp(logits, toks):
        return Completion(rid=0, prompt_len=1, tokens=np.asarray(toks),
                          arrival_step=0, admitted_step=0, finished_step=0,
                          first_logits=np.asarray(logits, np.float32))

    base = comp([0.0, 1.0, 5.0], [2, 1])
    failures = []
    chip_smoke.compare_runs(failures.append, "x", [base], [base], 1e-3)
    assert failures == []
    chip_smoke.compare_runs(failures.append, "x",
                            [comp([0.0, 1.0, 5.5], [2, 1])], [base], 1e-3)
    assert len(failures) == 1 and "logits differ" in failures[0]
    failures.clear()
    chip_smoke.compare_runs(failures.append, "x",
                            [comp([0.0, 1.0, 5.0], [1, 1])], [base], 1e-3)
    assert len(failures) == 1 and "clear top-2 gap" in failures[0]


_TP_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import json
import chip_smoke
spec = dict(arch="phi3-medium-14b", layers=0, reduced=True, slots=2,
            n_requests=3, prompt=(6, 12), gen=(3, 5), max_len=32, block=4,
            chunk=4)
failures = []
chip_smoke.phase_tp(failures.append, spec, seed=0)
print(json.dumps({"failures": failures}))
"""


def test_tensor_parallel_phase_on_four_host_devices(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _TP_SCRIPT, ROOT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {"failures": []}, out.stdout
    # 2 KV heads do not divide 4: the arena falls back to a full replica
    # on every device, never to device 0 alone
    assert any("replicated" in ln for ln in lines), out.stdout
