"""Share of the device's idle time inside the traced collects that no span
of the program explains: the innermost ``srt:`` span over it is a
``query``, ``task`` or ``op`` span (which exec ran, not what the host did)
or there is none.  Every gap is attributed, not the longest few, and a gap
is split wherever a span starts or ends (``program_spans.py``)."""

import program_spans


def read(run):
    reduced = program_spans.for_run(run)
    if reduced is None or not reduced["idle_s"]:
        return None
    return 100.0 * reduced["idle_unattributed_s"] / reduced["idle_s"]
