"""The control at a size a test run holds: the reference computed with
float8 operands in the program's place fails the tiny cell's limits,
where the program itself passes them, on three seeds."""
import jax
import pytest

import cells
import checks
import harness
import tiny


@pytest.mark.parametrize("seed", [(1 << 33) + 7, 11, 12])
def test_control_fails_where_program_passes(tmp_path, seed):
    bench, here, name = tiny.make(str(tmp_path))
    cell = cells.load_cell(name, bench, here)
    devices = jax.devices()[:1]
    run = harness.Run(cell, seed, devices)
    run.free_state()
    prog, ref = run.reference()
    ctrl = harness.against(harness.reference_run(
        cell, run.keys, run.prog, devices, mode="fp8"), ref)
    assert checks.judge(checks.numbers(prog, ref), tiny.LIMITS)[1]
    assert not checks.judge(checks.numbers(ctrl, ref), tiny.LIMITS)[1]
