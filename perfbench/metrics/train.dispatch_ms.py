"""Host time in the program's ``repro.train.step`` span, the call of the
jitted step up to its dispatch, per step of the window, from the program's
counters."""
from harness.program import STEP, per_step_ms


def read(run):
    return per_step_ms(run.counters, STEP, run.steps)
