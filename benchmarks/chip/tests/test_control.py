"""The check's control at a size a test run holds: the reference with
every matmul in int8 (W8A8) put in the program's place must come out not
correct, and the program correct, on the same seeds."""
import time

import pytest

import harness
import tiny


def _check(seed, control):
    cell, config, mix = tiny.mid_cell()
    harness.import_program()
    r = harness.Run(cell, config, mix, seed, 3.0, False, require_tpu=False,
                    control=control)
    r.setup(time.perf_counter())
    r.window()
    r.free_engine()
    check = r.check()
    return check, harness.correct_from(check)


@pytest.mark.parametrize("seed", [1, 2])
def test_program_is_correct(seed):
    check, ok = _check(seed, False)
    assert ok, check


@pytest.mark.parametrize("seed", [1, 2])
def test_int8_control_is_not(seed):
    check, ok = _check(seed, True)
    assert not ok and check["mean_gap"]["value"] > check["mean_gap"]["limit"]
