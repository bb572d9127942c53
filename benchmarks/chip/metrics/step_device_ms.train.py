"""Device time per launch of the training step's program
(``jit_train_step``), from the profiler trace of the window."""

MODULE = "jit_train_step"


def read(run, out):
    r = out.get("reduced")
    if r is None or not r.module_n.get(MODULE):
        return None
    return 1e3 * r.module_s[MODULE] / r.module_n[MODULE]
