"""The readers that split a snapshot publication by the program's own
spans (``topk.dispatch``, ``topk.readback`` inside ``snapshot.build``),
on a hand-made window of span totals."""
from __future__ import annotations

import types

import pytest

import cells

SPLIT = ["head_dispatch_ms", "head_readback_ms", "publish_host_ms",
         "readbacks_per_tick"]

#: the window's (count, seconds) per span: ten publications of eleven
#: heads each
SPAN_WINDOW = {"snapshot.build": (10, 0.5), "topk.dispatch": (110, 0.05),
               "topk.readback": (110, 0.4), "tick.reprice": (10, 0.03)}


def _read(name, spans):
    run = types.SimpleNamespace(
        trace=None, spans=spans,
        span_mean=lambda n: spans[n][1] / spans[n][0] if n in spans
        else None)
    return cells.reader("layer_metrics", f"{name}.saturate").read(run)


def test_publication_split_readers_add_up_to_the_build():
    assert _read("head_dispatch_ms", SPAN_WINDOW) == pytest.approx(5.0)
    assert _read("head_readback_ms", SPAN_WINDOW) == pytest.approx(40.0)
    assert _read("publish_host_ms", SPAN_WINDOW) == pytest.approx(5.0)
    assert _read("readbacks_per_tick", SPAN_WINDOW) == 11
    parts = sum(_read(n, SPAN_WINDOW) for n in SPLIT[:3])
    assert parts == pytest.approx(_read("snapshot_build_ms", SPAN_WINDOW))


@pytest.mark.parametrize("name", SPLIT)
def test_the_split_reads_nothing_from_a_program_without_the_head_spans(name):
    # what a program without the head spans leaves: the publication's
    # own span alone, or no publication at all
    assert _read(name, {"snapshot.build": (10, 0.5)}) is None
    assert _read(name, {}) is None
