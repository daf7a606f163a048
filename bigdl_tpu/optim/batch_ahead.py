"""The trainers' input, held one batch ahead of the device.

Every trainer loop (``LocalOptimizer.optimize``, ``DistriOptimizer``'s flat
and spec loops) syncs on a step's ``float(loss)`` once a step.  A
host-to-device copy started after that sync (154 MB a chip for a batch of
256 float32 images) would leave the device idle until it had crossed.
``BatchAhead`` moves that copy: the loop takes the batch of step N,
dispatches the step, and calls ``start()`` BEFORE it syncs (on step N-1,
whose loss the loops read one step late), so batch N+1 is fetched and put
while the device runs.  One thread, one batch in flight, no depth to
choose: ``device_put`` is asynchronous (a copy enqueued behind a running
program crosses beside it; measured on a TPU v5e, ``PERF.md`` section 6),
and where a loop blocks on the copy the host would have waited in the sync
anyway.
"""

from __future__ import annotations

import time
from typing import Callable, Iterator, Optional, Tuple

import jax

from bigdl_tpu.observability import tracer

PUT_METRIC = "put data into device"


def _base_dataset(dataset):
    """The underlying dataset of a (possibly chained) transformer
    wrapper — the object that owns the shuffle stream."""
    base = dataset
    while hasattr(base, "base"):
        base = base.base
    return base


def _sync_shuffles(dataset, epochs_completed: int) -> None:
    """Bring the dataset's shuffle stream to ``epochs_completed`` total
    shuffles.  The per-dataset seeded RNG makes shuffle replay
    deterministic, so a freshly constructed dataset on resume reproduces
    the permutation the interrupted run was iterating; a dataset already
    driven by a previous optimize() is left untouched."""
    base = _base_dataset(dataset)    # count on the underlying dataset so
    done = getattr(base, "_shuffles_done", 0)  # wrappers share a stream
    while done < epochs_completed:
        dataset.shuffle()
        done += 1
    base._shuffles_done = done


def _host_nbytes(data, labels) -> int:
    """Bytes ``h2d`` has to copy: those of the batch's arrays still on the
    host (0 for a batch the ingest ring staged on the device)."""
    return sum(int(getattr(a, "nbytes", 0))
               for a in jax.tree_util.tree_leaves((data, labels))
               if not isinstance(a, jax.Array))


class BatchAhead:
    """The batch stream of one ``optimize()``: epochs, the resume
    fast-forward, the host-to-device copy, and at most ONE batch beyond
    the one the device is using.

    ``open_epoch()`` returns the dataset's stream for one epoch (the
    shuffle stream already stands at that epoch): an iterator of
    ``(data, labels)`` pairs or ``MiniBatch``es.
    ``records_of(data)`` checks a host batch and returns the GLOBAL
    records it stands for; ``put(data, labels)`` returns the pair on the
    device.  ``epoch`` and ``records_done`` say where the stream stands
    (a resumed run: ``records_done`` records of ``epoch`` are passed
    over on the host before anything is put); ``epoch_records`` is the
    epoch's length in global records.

    The sequence of batches is exactly the one a loop without the
    look-ahead consumes: the rollover that ``loop.bookkeeping`` did after
    an epoch's last step (``_sync_shuffles`` + a fresh stream) happens
    here, when the batch AFTER that step is fetched.  The loop keeps the
    counters, triggers and snapshots, reading what they always read.
    """

    def __init__(self, dataset, open_epoch: Callable[[], Iterator],
                 records_of: Callable, put: Callable, metrics, *,
                 epoch: int, records_done: int, epoch_records: int):
        self._dataset = dataset
        self._open, self._records_of, self._put = open_epoch, records_of, put
        self._metrics = metrics
        self._epoch = epoch
        self._count = records_done      # of this epoch, fetched or skipped
        self._skip = records_done       # of them, still to pass over
        self._epoch_records = epoch_records
        self._stream = open_epoch()
        # the batch in flight: (data, labels, records), or the exception
        # its fetch or put raised, held until the loop asks for it
        self._flight: Optional[object] = None

    def start(self) -> None:
        """Fetch the next batch and start its copy.  Called between a
        step's dispatch and the sync that follows it, outside the step's
        watchdog (a slow decode is not a hung step).  An error here
        belongs to the NEXT step: it is held, so the running step's
        record, validation and checkpoint are written first, and
        ``take()`` raises it.  The batch started under a run's last step
        is dropped."""
        try:
            self._flight = self._fetch_and_put(ahead=True)
        except Exception as e:          # raised by take()
            self._flight = e

    def take(self) -> Tuple[object, object, int]:
        """``(data, labels, records)`` of the next step, on the device:
        the batch in flight, or (the first step of an ``optimize()``)
        one fetched and put in the open."""
        got, self._flight = self._flight, None
        if got is None:
            return self._fetch_and_put(ahead=False)
        if isinstance(got, Exception):
            raise got
        return got

    def _fetch_and_put(self, ahead: bool):
        with tracer.span("data.next"):
            if self._count >= self._epoch_records:
                # the batch in hand was its epoch's last
                self._epoch += 1
                self._count = 0
                _sync_shuffles(self._dataset, self._epoch - 1)
                self._stream = self._open()
            while True:
                data, labels = next(self._stream)
                records = self._records_of(data)
                if self._skip < records:
                    break
                # resume fast-forward: a fresh stream restarts the epoch;
                # pass over the records already trained so the resumed
                # run consumes exactly the batches an uninterrupted run
                # would
                self._skip -= records
            if self._skip:
                raise ValueError(
                    f"resume skip remainder {self._skip} is smaller than "
                    f"the batch ({records}): the batch size changed since "
                    "the snapshot; resume with the same batching to keep "
                    "the exact-resume contract")
        self._count += records
        t0 = time.time()
        # a staged ingest pipeline (ShardedDataSet(staging=True),
        # PrefetchToDevice) yields device-resident batches: the put is
        # then a no-op view and the span says so (run-report shows
        # ingest.h2d instead).  ``ahead``: a step was in flight, so the
        # device did not wait for this copy.
        with tracer.span("h2d", records=records, ahead=ahead,
                         staged=isinstance(data, jax.Array),
                         bytes=_host_nbytes(data, labels)):
            data, labels = self._put(data, labels)
        self._metrics.add(PUT_METRIC, (time.time() - t0) * 1e9)
        return data, labels, records
