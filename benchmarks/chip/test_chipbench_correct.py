"""``correct`` for each cell on the CPU rehearsal: true for a sound run,
false for the control (the reference in bfloat16 in the program's place)
and for each planted fault the cell can have, against the cell's limits."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import check, faults, harness  # noqa: E402

CELLS = ["qwen0.5b-int8-k4", "qwen0.5b-int8-k1"]
SEED = 2 ** 31 + 101


def correct(res):
    return all(c["ok"] for c in res["checks"])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    assert correct(harness.run_cell(cell, SEED, 0.2, False, True,
                                    time.perf_counter()))


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "token_altered", "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault):
    with faults.FAULTS[fault]():
        res = harness.run_cell(cell, SEED, 0.2, False, True,
                               time.perf_counter())
    assert not correct(res)


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell):
    import jax.numpy as jnp
    run = harness.Run(cell, SEED, True)
    run.setup()
    run.first_steps()
    run.free_program()
    ref = run.reference()
    ctl = run.reference(dtype=jnp.bfloat16, precision="default")
    judged = check.judge(check.numbers(ctl, ref, run.sizes, 0),
                         run.cell.checks["limits"])
    assert not all(c["ok"] for c in judged)
