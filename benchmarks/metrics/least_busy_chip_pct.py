"""Busy share of the least busy chip over the traced span (first traced
collect's start to the last one's end; busy = a program runs on the chip,
``mesh_trace.py``): with partition t on chip t the four
chips should work alike, and a chip that idles while another works is a
stage that ran in the wrong place.  ``mesh_trace.py`` prints every chip's
seconds.  Nothing where fewer than two chips worked."""

import mesh_trace


def read(run):
    reduced = mesh_trace.for_run(run)
    if reduced is None:
        return None
    least = min(c["busy_s"] for c in reduced["chips"].values())
    return 100.0 * least / reduced["span_s"]
