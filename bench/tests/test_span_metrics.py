"""The readers of the per-layer metrics built on the program's spans,
on synthetic spans: what each sums, over what base, which thread's
spans count, and ``None`` where the program names none of them."""

import importlib.util
import os
import threading

import pytest

from repro.obs.trace import Span

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
MAIN = threading.main_thread().ident
OTHER = MAIN + 1
MS = 1_000_000


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def sp(name, start_ms, dur_ms, tid=MAIN):
    return Span(name, int(start_ms * MS), int(dur_ms * MS), tid, None, None)


def train_spans():
    """Two steps on the main thread, each waiting on the packer inside
    ``train.next_batch``; the producer thread draws and packs three
    batches; a stray ``prefetch.wait`` on another thread (a nested
    packer's consumer) must not count."""
    return [sp("train.step", 0, 10), sp("train.next_batch", 0, 4),
            sp("prefetch.wait", 0, 3),
            sp("train.step", 10, 10), sp("train.next_batch", 10, 2),
            sp("prefetch.wait", 10, 1),
            sp("prefetch.source", 0, 5, OTHER),
            sp("prefetch.pack", 5, 2, OTHER),
            sp("prefetch.source", 7, 0.5, OTHER),
            sp("prefetch.pack", 7.5, 2, OTHER),
            sp("prefetch.source", 9.5, 0.5, OTHER),
            sp("prefetch.pack", 10, 2, OTHER),
            sp("prefetch.wait", 12, 7, OTHER)]


def test_wait_ms_per_step():
    read = reader("pipeline.wait_ms_per_step.train")
    assert read({"spans": train_spans()}) == pytest.approx((3 + 1) / 2)


def test_pack_ms_per_batch():
    read = reader("pipeline.pack_ms_per_batch.train")
    # 5 + 0.5 + 0.5 drawn, 3 x 2 packed, over three packs; the main
    # thread's spans are not the producer's.
    assert read({"spans": train_spans()
                 + [sp("prefetch.pack", 30, 9)]}) == pytest.approx(12 / 3)


def test_device_wait_ms_per_tick():
    read = reader("serve.device_wait_ms_per_tick")
    spans = [sp("cb.tick", 0, 20), sp("cb.admit", 0, 4),
             sp("cb.project", 1, 2), sp("cb.window", 4, 10),
             sp("cb.wait", 5, 8), sp("cb.retire", 14, 6),
             sp("cb.readback", 14, 5), sp("cb.tick", 20, 3),
             sp("cb.window", 20, 3), sp("cb.wait", 20, 2)]
    rec = {"spans": spans, "span_ticks": 4}
    assert read(rec) == pytest.approx((2 + 8 + 5 + 2) / 4)
    # The same base as serve.host_ms_per_tick, which counts the
    # read-back too and no longer the projection.
    host = reader("serve.host_ms_per_tick")(rec)
    assert host == pytest.approx((4 - 2 + 6 - 5 + 5) / 4)


@pytest.mark.parametrize("name", ["pipeline.wait_ms_per_step.train",
                                  "pipeline.pack_ms_per_batch.train",
                                  "serve.device_wait_ms_per_tick"])
def test_none_without_the_new_spans(name):
    """A program without the spans (the parent's) and a record without
    spans read ``None``, and never raise."""
    read = reader(name)
    old_train = [sp("train.step", 0, 10), sp("train.h2d", 1, 1),
                 sp("prefetch.pack", 0, 3, OTHER)]
    old_serve = [sp("cb.tick", 0, 10), sp("cb.window", 0, 5),
                 sp("cb.readback", 6, 2)]
    for rec in ({}, {"spans": []}, {"spans": old_train},
                {"spans": old_serve, "span_ticks": 3},
                {"spans": old_train, "span_ticks": 0}):
        assert read(rec) is None
