"""Host time per training step: the program's ``train.step`` spans less
their ``train.wait`` (the wait for the device's loss), per step, in the
window."""

from chipbench.program_spans import per_step_ms


def read(run, out):
    step = per_step_ms(run, "train.step")
    wait = per_step_ms(run, "train.wait")
    return None if step is None or wait is None else step - wait
