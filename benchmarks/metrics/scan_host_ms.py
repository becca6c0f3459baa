"""Milliseconds a traced collect spends in the host's part of the file
scan: self time of the ``srt:scan:host_decode`` (pyarrow reading a run of
row groups the device decoder gave up) and ``srt:scan:footer`` (open, prune,
split) spans, net of anything nested (``program_spans.py``)."""

import program_spans


def read(run):
    reduced = program_spans.for_run(run)
    if reduced is None:
        return None
    seconds = sum(row["self_s"] for name, row in reduced["spans"].items()
                  if name in ("srt:scan:host_decode", "srt:scan:footer"))
    return 1e3 * seconds / reduced["collects"]
