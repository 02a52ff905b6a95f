"""The comparison that decides ``correct``, driven through the harness on
the CPU at a tiny size (the look for a chip skipped): sound runs pass, the
control (the reference in float8 put in the program's place) fails one of
each cell's numbers, and each fault the cell can have, planted in the
program's timed path, makes ``correct`` false.

The tiny cells' limits (``fixtures/root/bench/limits``) were set from this
CPU's readings of the program and of the control, as the real cells' were
from the chip's."""
import os
import sys
import time

HERE = os.path.dirname(__file__)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import pytest  # noqa: E402

import run  # noqa: E402
from harness import Path  # noqa: E402

FIXTURE = Path(HERE) / "fixtures" / "root"
CELLS = ("tiny.train", "tiny-gelu.train")
_RESULTS = {}


def drive(cell, fault="", control=False, seed=2**31 + 17):
    return run.execute(FIXTURE, cell, seed, 1.0, False, control=control,
                       fault=fault, t_start=time.perf_counter())


def sound(cell):
    if cell not in _RESULTS:
        _RESULTS[cell] = drive(cell)
    return _RESULTS[cell]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = sound(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_number(cell):
    import check
    import harness

    limits = harness.find_cell(cell, FIXTURE).limits
    res = drive(cell, control=True, seed=2**31 + 23)
    # the control judged in the program's place is not correct; the
    # program's own numbers of the same run are
    assert not res["correct"], res["checks"]
    assert check.judge(res["readings"]["program"], limits)


@pytest.mark.parametrize("cell,fault", [("tiny.train", "unchanged"),
                                        ("tiny.train", "half_batch")])
def test_fault_makes_the_run_incorrect(cell, fault):
    res = drive(cell, fault=fault, control=False, seed=2**31 + 29)
    assert not res["correct"], res["checks"]


def test_reference_takes_the_programs_adam():
    import harness
    import inspect

    from repro.train.optimizer import adamw_update

    hp = harness.program_adam()
    params = inspect.signature(adamw_update).parameters
    assert hp == {k: params[k].default for k in ("b1", "b2", "eps")}
