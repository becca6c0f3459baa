"""Milliseconds a traced collect spends building broadcast sides on the
host's clock: the spans ``srt:broadcast:build`` (one per broadcast exchange
a collect: ``.collect`` runs the child's partitions, ``.upload`` packs them
into the one batch every probe shares) and ``srt:join:adaptive.materialize``
(the adaptive join running its build side to measure it).  Nothing where
the program has neither span."""

import join_trace
import program_spans


def read(run):
    reduced = program_spans.for_run(run)
    if reduced is None:
        return None
    rows = [reduced["spans"][name] for name in join_trace.BUILD_SPANS
            if name in reduced["spans"]]
    if not rows:
        return None
    return 1e3 * sum(row["s"] for row in rows) / reduced["collects"]
