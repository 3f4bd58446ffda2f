"""The control: the plain reference computed with every matrix product in
float8 (``reference.mm_fp8``), put in the program's place, is judged not
correct, while the program is; at a size a CPU test can hold, on three
seeds.  ``bench/calibrate.py`` reads the same at the cell's own size on
the chip, where the limits were set."""
import pytest

from bench import harness
from bench.tests.cells import devices, tiny


@pytest.fixture(scope="module")
def train_readings():
    cell = tiny("qwen2-0.5b.train.s4k")
    drv = harness.load_module(harness.BENCH / "drivers" / "train.py")
    program = drv.Program(cell, devices(cell))
    rule = cell.limits["leaf_rule"]
    out = []
    for seed in (5, 6, 2**33 + 7):
        _, prog = program.check_steps(seed)
        ref = drv.reference_readings(cell, seed, devices(cell), None,
                                     "float32")
        ctl = drv.reference_readings(cell, seed, devices(cell), None,
                                     "float8")
        out.append((cell, drv.compare(prog, ref, rule),
                    drv.compare(ctl, ref, rule)))
    return out


def test_train_program_is_correct_and_control_is_not(train_readings):
    for cell, prog, ctl in train_readings:
        assert harness.judge(prog, cell.limits)[1], prog
        assert not harness.judge(ctl, cell.limits)[1], ctl
        assert ctl["grad_gap"] > 1.5 * prog["grad_gap"]
