"""Host time per step in the jitted step's call (flattening and
donating the parameter and AdamW trees, the launch): the program's
``train.launch`` spans, per step, in the window."""

from chipbench.program_spans import per_step_ms


def read(run, out):
    return per_step_ms(run, "train.launch")
