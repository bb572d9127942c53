"""Mean time a record waits in its MDT journal, from its ``cr_time`` to
the routing round that reads it: the record-weighted mean of the
program's ``journal.wait`` spans in the window."""

from chipbench.program_spans import mean_wait_ms


def read(run, out):
    return mean_wait_ms(run, "journal.wait")
