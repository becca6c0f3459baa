"""The readers of PR 37's spans (``eager_idle_ms``, ``sync_ms``,
``decode_host_ms``): nothing on traces that lack their spans, and the
arithmetic on a reduction that has them."""

import os

import pytest

import program_spans as PS

DATA = os.path.join(os.path.dirname(__file__), "data")
#: PR 26's parquet collect: ``srt:`` spans, but no eager, sync, chunk_read
#: or pages span among them
SPANS = os.path.join(DATA, "trace_spans_small.pbtxt")
#: PR 25's: a program from before the spans existed
NO_SPANS = os.path.join(DATA, "trace_small.pbtxt")
READERS = ("eager_idle_ms", "sync_ms", "decode_host_ms")


def _read(monkeypatch, names, path=None, reduced=None):
    import run as R
    if reduced is not None:
        monkeypatch.setattr(PS, "for_run", lambda run: reduced)
    else:
        monkeypatch.setattr(PS, "trace_file", lambda run: path)
    run = {"trace": {"collects": [{}]}, "cell": {"name": "x"},
           "window": {"collects": 4}}
    return R.read_metrics(list(names), run)


@pytest.mark.parametrize("name", READERS)
def test_nothing_on_a_trace_without_the_programs_spans(monkeypatch, name):
    assert _read(monkeypatch, [name], path=NO_SPANS) == {}


def test_a_parents_trace_reads_sync_alone(monkeypatch):
    """A trace of a program without the new spans: no eager or decode
    phase to read, not 0; ``sync_ms`` reads the old sync spans (none in
    this collect: 0)."""
    assert _read(monkeypatch, READERS, path=SPANS) == {"sync_ms": 0.0}


def _row(n=1, s=0.0, self_s=0.0, idle_s=0.0):
    return {"n": n, "s": s, "self_s": self_s, "idle_s": idle_s}


def test_the_readers_on_a_reduction_that_has_the_spans(monkeypatch):
    reduced = {
        "collects": 2,
        "spans": {
            "srt:eager:batch.sliced": _row(9, 0.30, 0.20, 0.25),
            "srt:eager:top_n.merge": _row(1, 0.10, 0.05, 0.05),
            "srt:sync:batch.num_rows": _row(9, 0.04, 0.04, 0.01),
            "srt:scan:device_decode": _row(3, 2.00, 0.10, 0.0),
            "srt:scan:chunk_read": _row(18, 0.12, 0.12, 0.0),
            "srt:scan:pages": _row(9, 0.50, 0.48, 0.0),
        },
        "categories": {
            "eager": {"s": 0.40, "self_s": 0.25, "idle_s": 0.30},
            "sync": {"s": 0.60, "self_s": 0.60, "idle_s": 0.01},
            "scan": {"s": 2.00, "self_s": 0.70, "idle_s": 0.0},
        },
    }
    got = _read(monkeypatch, READERS, reduced=reduced)
    assert got["eager_idle_ms"] == pytest.approx(150.0)     # 0.30 s / 2
    assert got["sync_ms"] == pytest.approx(300.0)           # 0.60 s / 2
    assert got["decode_host_ms"] == pytest.approx(300.0)    # 0.60 s / 2
    # the eager spans present but no idle under them: 0, not nothing
    reduced["categories"]["eager"]["idle_s"] = 0.0
    assert _read(monkeypatch, ["eager_idle_ms"],
                 reduced=reduced)["eager_idle_ms"] == 0.0
