"""Milliseconds a traced collect spends with the host blocked in a fetch:
the ``srt:d2h:*`` spans (``fused_collect.fetch``, ``bulk_device_get``,
``prepacked_device_get``, ``device_get.fallback``).  The wait for the
device to finish the programs before the copy is inside
(``program_spans.py``)."""

import program_spans


def read(run):
    return program_spans.category_ms(run, "d2h")
