"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run on the CPU at toy sizes (the chip look is
skipped) through ``run.run_cell``, with the program's engine wrapped so
that one fault a serving cell can have is planted where the work is
done.  The faults a training cell can have (half the batch left out) and
a cell on several chips can have (the exchange left out) do not apply to
a one-chip serving cell.
"""
import pytest

import program
import run
from conftest import tiny_cell

KV_LEAVES = ("c_kv", "k_rope", "k", "v")


def _broken(fault):
    class Broken(program.Server):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            inner = self.engine.mixed_step
            vocab = self.cfg.vocab

            def mixed_step(cache, *args, **kw):
                new, logits, toks = inner(cache, *args, **kw)
                if fault == "state_unchanged":
                    # the step computes, but its KV writes never land
                    new = dict(new, **{k: cache[k] for k in KV_LEAVES
                                       if k in cache})
                elif fault == "token_altered":
                    toks = toks.at[:, 1].set((toks[:, 1] + 1) % vocab)
                return new, logits, toks

            self.engine.mixed_step = mixed_step
    return Broken


def _run(lane, factory=None, seed=3):
    result, _, _, _ = run.run_cell(tiny_cell(lane, max_logit_gap=0.06),
                                   seed, 6, False, require_chip=False,
                                   server_factory=factory)
    return result


@pytest.mark.parametrize("lane", ["mla", "gqa"])
def test_sound_run_is_correct(lane):
    assert _run(lane)["correct"]


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
@pytest.mark.parametrize("lane", ["mla", "gqa"])
def test_fault_is_caught(lane, fault):
    result = _run(lane, _broken(fault))
    assert not result["correct"]
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
