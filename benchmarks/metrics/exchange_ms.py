"""Milliseconds a traced collect spends in the mesh exchange on the host's
side: the spans ``srt:shuffle:mesh_exchange`` (one per exchange that rode
the mesh plane; inside it ``.map`` = the map programs' launches, ``.counts``
= the one read of the pieces' row counts, ``.collective`` = the launch of
the ``all_to_all`` program, ``.shrink`` = handing each chip's shard on).
Nothing where no exchange rode the plane (one executor, or a program from
before the plane chose itself)."""

import mesh_trace
import program_spans


def read(run):
    reduced = program_spans.for_run(run)
    if reduced is None:
        return None
    row = reduced["spans"].get(mesh_trace.EXCHANGE_SPAN)
    if not row or not row["n"]:
        return None
    return 1e3 * row["s"] / reduced["collects"]
