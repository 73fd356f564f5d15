"""BENCHMARK.json keeps to the benchmark's contract, every name resolves
to its file, and a run without an accelerator exits non-zero with no
result."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import run

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_keys_names_and_files():
    s = _spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= s["run_seconds"] <= 51
    e2e = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in e2e
    cfgs = {c["name"] for c in s["configs"]}
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] \
            == c["reduced"]
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in cfgs
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "cells" / f"{w['name']}.json").is_file()
        cell = run.load_cell(w["name"])
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2 and cell["per_layer"]
        for m in cell["per_layer"]:
            assert m["moves"] in names
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert len(json.dumps(s)) < 64 * 1024


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = _spec()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", "1",
         "--seconds", "5", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
