"""Host time per step in the trackers' journal writes (``step_commit``,
``heartbeat``) and the checkpoint submit check: the program's
``train.track`` spans, per step, in the window."""

from chipbench.program_spans import per_step_ms


def read(run, out):
    return per_step_ms(run, "train.track")
