"""Milliseconds a traced collect the host waits on the device in blocking
readbacks: the ``srt:sync:*`` spans, each counted where no other ``sync``
span encloses it (``join.readback``, ``agg.group_count``, and the
``batch.num_rows`` readback of a batch's row count where the program has
it; ``program_spans.py``)."""

import program_spans


def read(run):
    return program_spans.category_ms(run, "sync")
