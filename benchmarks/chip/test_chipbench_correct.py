"""``correct`` for each cell on the CPU rehearsal: true for a sound run,
false for the control (the reference in bfloat16 in the program's place)
and for each planted fault the cell can have, against the cell's limits.

A cell on several chips runs on as many virtual CPU devices, whose count
is fixed when JAX starts: its cases run in one child process."""
import contextlib
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")
sys.path.insert(0, HERE)

from chipbench import catalog, check, faults, harness  # noqa: E402

CELLS = ["qwen0.5b-int8-k4", "qwen0.5b-int8-k1", "qwen0.5b-mesh4-int8-k4"]
SEED = 2 ** 31 + 101


def chips(cell):
    return catalog.find_cell(cell).chips


def correct(res):
    return all(c["ok"] for c in res["checks"])


def outcome(cell, case):
    """``correct`` of one case of ``cell``: ``sound``, ``control`` or a
    planted fault."""
    if case == "control":
        import jax.numpy as jnp
        run = harness.Run(cell, SEED, True)
        run.setup()
        run.first_steps()
        run.free_program()
        ref = run.reference()
        ctl = run.reference(dtype=jnp.bfloat16, precision="default")
        judged = check.judge(check.numbers(ctl, ref, run.sizes, 0),
                             run.cell.checks["limits"])
        return all(c["ok"] for c in judged)
    with (faults.FAULTS[case]() if case != "sound"
          else contextlib.nullcontext()):
        return correct(harness.run_cell(cell, SEED, 0.2, False, True,
                                        time.perf_counter()))


CHILD = """
import json, sys
sys.path[:0] = [{here!r}, {src!r}]
import test_chipbench_correct as t
print(json.dumps({{c: t.outcome(sys.argv[1], c) for c in sys.argv[2:]}}))
"""


@pytest.fixture(scope="module")
def many_chip_outcomes():
    """``{cell: {case: correct}}`` of the cells on several chips."""
    out = {}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cell in (c for c in CELLS if chips(c) > 1):
        cases = ["sound", "control", *faults.faults_for(chips(cell))]
        proc = subprocess.run(
            [sys.executable, "-c", CHILD.format(here=HERE, src=SRC), cell,
             *cases],
            env=env, capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-4000:]
        out[cell] = json.loads(proc.stdout.strip().splitlines()[-1])
    return out


def judged(cell, case, request):
    if chips(cell) > 1:
        return request.getfixturevalue("many_chip_outcomes")[cell][case]
    return outcome(cell, case)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, request):
    assert judged(cell, "sound", request)


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS for f in faults.faults_for(chips(c))])
def test_planted_fault_is_not_correct(cell, fault, request):
    assert not judged(cell, fault, request)


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell, request):
    assert not judged(cell, "control", request)
