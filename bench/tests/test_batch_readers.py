"""The reason cell's new readers, and the GQA lane at its head ratio.

``hbm.py``'s bytes of a decode step (a round of one step) must match a
hand count of both config files.  ``kv_read_per_live.batch`` and
``hbm_pct.batch`` must read, on a whole tiny traced run on the CPU,
what the program's ``sched.round`` counters give by hand (the run's
device trace, which a CPU has not, is made up: a window and one
device), and nothing from a run without the program's spans, without a
trace, or from a program whose rounds lack the counters.  The same
runs drive the GQA lane at 4 query heads per KV head, the ratio of the
phi3 cell, with every answer decoding across a 16-position block: the
program must pass the comparison and the fp8 control must fail it, on
three seeds."""
import json
import sys
from pathlib import Path

import pytest

import hbm
import repro.runtime
import run
import spans
from conftest import TINY

ROOT = Path(__file__).resolve().parents[2]

# published widths, by hand: per layer the attention projections, the
# three MLP matrices and two norms; then the final norm and the LM head
PHI3_LAYER = 5120 * (5120 + 2 * 1280 + 5120) + 3 * 5120 * 17920 + 2 * 5120
MINICPM3_LAYER = (2560 * 768 + 768 + 768 * 40 * (64 + 32)
                  + 2560 * (256 + 32) + 256 + 2 * 256 * 40 * 64
                  + 40 * 64 * 2560 + 3 * 2560 * 6400 + 2 * 2560)
HAND = {   # config file: (weight bytes, KV bytes of one position), bf16
    "phi3-medium-14b.pp4": (2 * (10 * PHI3_LAYER + 5120 + 5120 * 32064),
                            2 * 10 * 128 * 10 * 2),       # K and V
    "minicpm3-4b": (2 * (62 * MINICPM3_LAYER + 2560 + 2560 * 73448),
                    (256 + 32) * 62 * 2),             # latent and RoPE
}

GQA4 = dict(TINY["gqa"], name="tiny-gqa4", hidden_size=128,
            intermediate_size=256, num_attention_heads=8,
            num_key_value_heads=2)
BACKLOG = {"arrival": "backlog", "size_seed": 9, "backlog_per_slot": 6,
           "prompt_tokens": {"median": 20, "sigma": 0.5, "min": 8,
                             "max": 40},
           "output_tokens": {"median": 24, "sigma": 0.4, "min": 17,
                             "max": 40}}
LIMIT = 0.1      # toy sizes: program <= 0.018, control >= 0.39 (CPU)
NEW = ("kv_read_per_live.batch", "hbm_pct.batch")
COUNTERS = ("decode_steps", "kv_live_positions", "kv_read_positions")


def _cell():
    return dict(
        name="tiny-gqa4.reason", chips=1, config=GQA4, mix=BACKLOG,
        params={"n_slots": 4, "max_len": 128, "block_size": 16,
                "chunk_size": 8, "trace": {"last_s": 2},
                "check": {"sample": 3, "max_logit_gap": LIMIT,
                          "min_tokens_compared": 8}},
        end_to_end=[], per_layer=[{"name": n, "unit": "x"} for n in NEW])


def _served(result, limit=LIMIT):
    r = result["readings"]
    assert result["correct"] and r["max_logit_gap"] <= limit
    assert r["control"]["max_logit_gap"] > limit
    assert r["control"]["tokens_compared"] == r["tokens_compared"]
    # every answer compared is 17 tokens or more: past a block boundary
    assert r["tokens_compared"] >= 17 * r["requests_compared"] > 0


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "TRACE_DIR", tmp_path_factory.mktemp("trace"))
        result, _, _, rec = run.run_cell(_cell(), 1, 2, True,
                                         require_chip=False, control=True)
    return result, rec, repro.runtime.spans.snapshot()


def _rounds_by_hand(rec, snap):
    """The window's ``sched.round`` spans of the newest scheduler, each
    beside the harness's round, with its end on the window's clock."""
    sched = max(s.ids["sched"] for s in snap if s.name == "sched.round")
    mine = sorted((s for s in snap if s.name == "sched.round"
                   and s.ids["sched"] == sched), key=lambda s: s.start_ns)
    t0 = run.T_PROCESS + rec["setup_s"]
    return [(r, s, s.end_ns * 1e-9 - t0)
            for r, s in zip(rec["rounds"], mine[-len(rec["rounds"]):])]


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_step_bytes_match_a_hand_count(name):
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    w, kv = HAND[name]
    assert hbm.weight_bytes(c) == w
    assert hbm.kv_bytes_per_position(c) == kv
    one = dict(prefill_rows=0, decode_steps=1, kv_live_positions=5000,
               decode_rows=6)
    assert hbm.round_bytes(c, one) == w + 5006 * kv
    assert hbm.round_bytes(c, dict(one, prefill_rows=2)) == 2 * w + 5006 * kv
    if name.startswith("phi3"):
        assert (w, kv) == (7_144_294_400, 51_200)


def test_kv_read_per_live_is_the_counters_ratio(traced):
    result, rec, snap = traced
    rounds = [s for _, s, end in _rounds_by_hand(rec, snap)
              if end <= rec["seconds"]]
    live = sum(s.counters["kv_live_positions"] for s in rounds)
    reads = sum(s.counters["kv_read_positions"] for s in rounds)
    assert live > 0
    got = result["metrics"]["kv_read_per_live.batch"]["value"]
    assert got == pytest.approx(reads / live)
    assert 1 < got < 128      # live contexts are short of max_len 128


def test_hbm_pct_on_a_made_up_trace(traced):
    result, rec, snap = traced
    assert "hbm_pct.batch" not in result["metrics"]   # no device planes
    window = 1.5
    fake = dict(rec, device={"kind": "TPU v5 lite"},
                trace=dict(window_s=window, busy_s=1.0, n_devices=1,
                           modules={}))
    c = rec["config"]
    w, kv = hbm.weight_bytes(c), hbm.kv_bytes_per_position(c)
    traced_rounds = [s.counters for r, s, _ in _rounds_by_hand(rec, snap)
                     if r["traced"]]
    assert any(k["decode_steps"] for k in traced_rounds)
    want = sum(k["decode_steps"] * w + (k["prefill_rows"] > 0) * w
               + (k["kv_live_positions"]
                  + k["decode_steps"] * k["decode_rows"]) * kv
               for k in traced_rounds)
    got = run.read_metric("hbm_pct.batch", fake)
    assert got == pytest.approx(100.0 * want / (window * 819e9))
    assert run.read_metric("hbm_pct.batch", dict(fake, trace=None)) is None


def test_nothing_without_spans_or_counters(traced, monkeypatch):
    _, rec, _ = traced
    fake = dict(rec, device={"kind": "TPU v5 lite"},
                trace=dict(window_s=1.5, busy_s=1.0, n_devices=1,
                           modules={}))
    loaded = spans.load(fake)
    for r in loaded["rounds"]:            # a program without the counters
        r["counters"] = {k: v for k, v in r["counters"].items()
                         if k not in COUNTERS}
    monkeypatch.setattr(spans, "load", lambda run: loaded)
    for name in NEW:
        assert run.read_metric(name, fake) is None
    monkeypatch.undo()
    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    for name in NEW:
        assert run.read_metric(name, fake) is None


def test_gqa_four_heads_per_kv_head_across_blocks(traced):
    """4 query heads share each KV head, and every answer decodes past a
    16-position block boundary of the posit16 arena."""
    assert GQA4["num_attention_heads"] == 4 * GQA4["num_key_value_heads"]
    _served(traced[0])
    for seed in (2, 3):
        result, _, _, _ = run.run_cell(_cell(), seed, 2, False,
                                       require_chip=False, control=True)
        _served(result)
