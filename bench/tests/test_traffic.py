"""The generator: same seed, same trace; other seeds, the same work."""
import json
from pathlib import Path

import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def test_same_seed_same_trace():
    cell = {"rate_per_s": 0.6, "n_slots": 16}
    a = traffic.generate(_mix("chat"), cell, 2**31 + 99, 50, 73448)
    b = traffic.generate(_mix("chat"), cell, 2**31 + 99, 50, 73448)
    assert a == b


def test_seeds_share_one_schedule():
    """Only the token ids depend on the seed; lengths and due times are
    the mix's own, so every seed offers the same work."""
    cell = {"rate_per_s": 0.6, "n_slots": 16}
    runs = [traffic.generate(_mix("chat"), cell, s, 50, 73448)
            for s in (1, 2, -3, 2**40 + 7)]
    n = round(0.6 * 50)
    for reqs in runs:
        assert len(reqs) == n
        assert all(0 <= r.due_s < 50 for r in reqs)
        assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
        assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in reqs] \
            == [(r.due_s, len(r.prompt), r.max_new_tokens) for r in runs[0]]
    assert runs[0] != runs[1]
    spec = _mix("chat")["prompt_tokens"]
    assert all(spec["min"] <= len(r.prompt) <= spec["max"] for r in runs[0])


def test_backlog_is_queued_at_the_start():
    mix = _mix("reason")
    reqs = traffic.generate(mix, {"n_slots": 8}, 5, 50, 32064)
    assert len(reqs) == mix["backlog_per_slot"] * 8
    assert all(r.due_s == 0.0 for r in reqs)
    spec = mix["output_tokens"]
    assert all(spec["min"] <= r.max_new_tokens <= spec["max"] for r in reqs)
    assert all(1 <= t < 32064 for r in reqs for t in r.prompt)
