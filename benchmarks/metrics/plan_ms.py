"""Milliseconds a traced collect spends planning: the program's
``srt:plan:parse`` (SQL text to logical plan) and ``srt:plan:physical``
(every ``plan_for_collect``, re-plans included) spans, from the profiler's
clock (``program_spans.py``)."""

import program_spans


def read(run):
    return program_spans.category_ms(run, "plan")
