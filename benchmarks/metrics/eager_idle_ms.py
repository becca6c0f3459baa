"""Milliseconds of device-idle time a traced collect whose innermost
``srt:`` span is an ``srt:eager:*`` span: the chip waiting while the host
launches programs past the kernel cache one by one (``ColumnarBatch``
primitives, the limit's top-n and merge, an encoded column decoded, the
parquet decoder's own programs; ``program_spans.py``).  Nothing on a trace
that holds no such span (a program from before them), not 0."""

import program_spans


def read(run):
    reduced = program_spans.for_run(run)
    if reduced is None or not any(name.startswith("srt:eager:")
                                  for name in reduced["spans"]):
        return None
    return program_spans.category_ms(run, "eager", "idle_s")
