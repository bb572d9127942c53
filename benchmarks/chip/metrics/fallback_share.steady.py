"""Share of the records dispatched that took the per-record loop
instead of whole-batch dispatch: Σ count of the program's
``proxy.dispatch.fallback`` spans over Σ count of ``proxy.dispatch``,
in the window, %."""

from chipbench.program_spans import totals


def read(run, out):
    dispatch = totals(run, "proxy.dispatch")
    if not dispatch or not dispatch[0]:
        return None
    return 100.0 * totals(run, "proxy.dispatch.fallback")[0] / dispatch[0]
