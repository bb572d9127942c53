"""Mean time a record waits in a member's outbox, from its dispatch to
the fetch that hands it over: the record-weighted mean of the program's
``proxy.outbox_wait`` spans in the window."""

from chipbench.program_spans import mean_wait_ms


def read(run, out):
    return mean_wait_ms(run, "proxy.outbox_wait")
