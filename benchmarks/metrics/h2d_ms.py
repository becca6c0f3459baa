"""Milliseconds a traced collect spends uploading: the ``srt:h2d:*`` spans
(``arrow_to_device``, ``HostToDevice.upload``), host time from the
profiler's clock; 0 where the scan's upload cache holds
(``program_spans.py``)."""

import program_spans


def read(run):
    return program_spans.category_ms(run, "h2d")
