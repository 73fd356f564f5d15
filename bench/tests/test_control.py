"""The control must come out not correct: the reference computed in fp8
(the nearest precision below the bf16 the configs state), put in the
program's place on the same prompts, at toy sizes on the CPU, on three
seeds, against a limit set as the chip cells' limits are (above the
program's readings, below the control's)."""
import pytest

import run
from conftest import tiny_cell

LIMIT = 0.06     # toy sizes: program <= 0.016, control >= 0.136 (CPU)


@pytest.mark.parametrize("lane", ["mla", "gqa"])
def test_control_fails_where_the_program_passes(lane):
    for seed in (1, 2, 3):
        result, _, _, _ = run.run_cell(
            tiny_cell(lane, max_logit_gap=LIMIT), seed, 6, False,
            require_chip=False, control=True)
        r = result["readings"]
        assert result["correct"]
        assert r["max_logit_gap"] <= LIMIT
        assert r["control"]["max_logit_gap"] > LIMIT
        assert r["control"]["tokens_compared"] == r["tokens_compared"]
