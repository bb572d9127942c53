"""Mean time a record waits in a shard's ingest buffer, from its offer
to the dispatch pass that takes it: the record-weighted mean of the
program's ``proxy.buffer_wait`` spans in the window."""

from chipbench.program_spans import mean_wait_ms


def read(run, out):
    return mean_wait_ms(run, "proxy.buffer_wait")
