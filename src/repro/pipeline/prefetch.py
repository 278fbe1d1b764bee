"""Async packing (stage 4 of the schedule pipeline).

Even a cache-hit lookup does host work (fingerprinting, external-input
packing, the occasional cold ``pack_batch``), and the device should
never wait on the host.  :class:`AsyncPacker` runs the whole
fingerprint → cache → bucket → pack → device-put chain on a background
thread with a bounded queue of ready batches — the same prefetch
discipline as ``data/loader.py`` (it IS ``BackgroundPrefetcher``
underneath), applied to schedule compilation.

Ordering is preserved (single producer, FIFO queue); exceptions raised
while packing surface on the consumer thread at the batch where they
occurred; ``close()`` stops the producer and drains the queue.

Transient faults (a :class:`~repro.dist.fault.SimulatedFailure`, the
class chaos injection and simulated node failures raise — retry-able by
contract) are retried in place up to ``retries`` times before
surfacing, WITHOUT dropping the item being packed: a blip on the
background thread must not silently lose a batch from the stream.
Deterministic errors (bad data, shape mismatches) are never retried.

Spans: on the producer thread ``prefetch.source`` (drawing the next
item from the source, where a composed epoch is built) and
``prefetch.pack`` (packing it), both with ``seq``, the batch's index in
the stream; on the consumer thread ``prefetch.wait`` (taking the next
ready batch) with the ``seq`` it consumes, which is the ``seq`` of the
``prefetch.pack`` that produced it (FIFO), and ``ready``, the batches
queued on entry (0: the consumer waited on the producer).
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator

from repro.data.loader import BackgroundPrefetcher
from repro.dist.fault import SimulatedFailure, chaos_fire
from repro.obs import trace


class AsyncPacker:
    """Background-thread map of ``pack_fn`` over ``source`` with a
    bounded queue (``depth`` batches deep) — generic enough to pack
    schedules (``SchedulePipeline.prefetch``) or to stage plain token
    batches onto the device (``examples/train_lm.py``)."""

    def __init__(self, source: Iterable[Any],
                 pack_fn: Callable[[Any], Any], *, depth: int = 2,
                 retries: int = 2):
        self._source: Iterator[Any] = iter(source)
        self._pack_fn = pack_fn
        self._retries = retries
        self.packed = 0                   # batches produced so far
        self.consumed = 0                 # batches handed to the consumer
        self.transient_retries = 0        # SimulatedFailures absorbed
        self._bg = BackgroundPrefetcher(self._produce, depth=depth)

    def _produce(self) -> Any:
        with trace.span("prefetch.source", seq=self.packed):
            item = next(self._source)     # StopIteration ends the stream
        attempt = 0
        # Explicit begin/end (not the context manager): the producer
        # runs on the prefetch thread, and a retried pack is still ONE
        # span — `retries` lands on it as an end-time attribute.
        h = trace.begin("prefetch.pack", seq=self.packed)
        try:
            while True:
                try:
                    chaos_fire("prefetch")
                    out = self._pack_fn(item)
                    break
                except SimulatedFailure:
                    # Transient by contract: retry the SAME item so the
                    # stream never loses a batch; give up after the
                    # budget (the consumer then sees the failure at
                    # this batch).
                    attempt += 1
                    if attempt > self._retries:
                        raise
                    self.transient_retries += 1
        finally:
            trace.end(h, retries=attempt)
        self.packed += 1
        return out

    def __iter__(self) -> "AsyncPacker":
        return self

    def __next__(self) -> Any:
        seq = self.consumed
        ready = self._bg.ready if trace.enabled() else 0
        with trace.span("prefetch.wait", seq=seq, ready=ready):
            item = next(self._bg)
        self.consumed = seq + 1
        return item

    def close(self) -> None:
        self._bg.close()

    def __enter__(self) -> "AsyncPacker":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
