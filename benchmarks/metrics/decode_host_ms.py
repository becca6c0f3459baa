"""Milliseconds a traced collect spends in the host's phases of the parquet
device decode: self time of the ``srt:scan:chunk_read`` (a column chunk's
compressed bytes off the file) and ``srt:scan:pages`` (page headers,
decompression, the hybrid run walk, the dictionaries' union) spans inside
``srt:scan:device_decode`` (``program_spans.py``).  Nothing on a trace that
holds neither span (a program from before them), not 0."""

import program_spans

SPANS = ("srt:scan:chunk_read", "srt:scan:pages")


def read(run):
    reduced = program_spans.for_run(run)
    if reduced is None or not any(name in reduced["spans"]
                                  for name in SPANS):
        return None
    seconds = sum(reduced["spans"][name]["self_s"] for name in SPANS
                  if name in reduced["spans"])
    return 1e3 * seconds / reduced["collects"]
