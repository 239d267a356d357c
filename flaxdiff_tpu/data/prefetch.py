"""Pipelined host-side transforms: overlap per-batch CPU work (text
encoding, augmentation) with device steps.

SURVEY §7.3(4): the reference runs its CLIP text tower INSIDE the jitted
train step (reference general_diffusion_trainer.py:275,292), spending MXU
cycles on a frozen encoder every step; round-1 of this framework encoded
on the host synchronously, serializing input against the device. This
module is the third option: encode on the host in a background thread,
`depth` batches ahead, so encoding cost hides behind device compute
entirely when encode_time <= step_time (measured: a CLIP-L text tower on
77 tokens is ~5-15 ms on host vs ~100+ ms UNet steps, so prefetch wins
over in-jit — which also pays HBM for the frozen tower's weights — and
over blocking host encode; see bench note in scripts/bench_text_encode.py).
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator, TypeVar

T = TypeVar("T")
U = TypeVar("U")

_SENTINEL = object()


def prefetch_map(fn: Callable[[T], U], it: Iterator[T],
                 depth: int = 2) -> Iterator[U]:
    """Apply `fn` to items of `it` in a daemon thread, keeping up to
    `depth` results ready. Order-preserving. Exceptions in `fn` or the
    source iterator re-raise at the consumer's next() (the data-layer
    fault-surfacing behavior of reference online_loader.py:980-988).

    Closing/abandoning the returned generator stops the worker: its
    queue puts poll a stop flag, so a consumer that walks away (common
    in tests and chunked training loops) doesn't leave a thread blocked
    on a full queue for the life of the process."""
    if depth < 1:
        raise ValueError("depth must be >= 1")
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def put(item) -> bool:
        """Blocking put that gives up when the consumer is gone."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in it:
                if not put(fn(item)):
                    return
        except BaseException as e:  # surfaced on the consumer side
            # structured visibility BEFORE the re-raise lands: a consumer
            # that swallows the exception (or dies with it) still leaves
            # the pipeline failure in the resilience event stream
            from ..resilience.events import record_event
            record_event("pipeline_error", "data.prefetch",
                         detail=f"{type(e).__name__}: {e}")
            put((_SENTINEL, e))
            return
        put((_SENTINEL, None))

    t = threading.Thread(target=worker, daemon=True,
                         name="flaxdiff-prefetch")
    t.start()

    try:
        while True:
            got = q.get()
            if isinstance(got, tuple) and len(got) == 2 \
                    and got[0] is _SENTINEL:
                if got[1] is not None:
                    raise got[1]
                return
            yield got
    finally:
        stop.set()


class prefetch_to_device:
    """H2D upload prefetch: apply `put_fn` (host numpy batch -> sharded
    device arrays, e.g. `DiffusionTrainer.put_batch`) in a background
    thread, keeping up to `depth` uploaded batches ready — the host-to-
    device copy overlaps device compute instead of serializing with it,
    even on steps where the consumer closes dispatch (telemetry-sampled
    steps). Order-preserving; exceptions re-raise at the consumer's
    `next()` like `prefetch_map`.

    Unlike the bare generator, this wrapper exposes `close()` with a
    bounded worker join: the fit loop shares its source iterator with
    other consumers (validation pulls real batches between fit chunks),
    so on exit the worker must actually STOP before anyone else touches
    the iterator — two threads driving one generator is a race, not
    just a lost batch. Up to `depth + 1` prefetched batches are
    discarded on close (an accepted cost on streaming data; documented
    in `DiffusionTrainer.fit`). A worker wedged inside the source
    iterator past `join_timeout` is abandoned (daemon) with a
    `pipeline_error`-adjacent warning event rather than hanging the
    caller's shutdown.

    `screen` (ISSUE 17) is the pre-upload batch screen: called on each
    HOST batch BEFORE `put_fn` (i.e. before any H2D copy); a non-None
    reason quarantines the batch (noted in `quarantine` when given) and
    skips it deterministically — blast radius one batch, never the step
    loop. `state_dict()` exposes the in-flight window (submitted vs
    delivered vs screened) so the data plane can account for every
    batch the pipeline ever touched — the "zero stranded batches"
    acceptance of tests/test_data_chaos.py."""

    def __init__(self, put_fn: Callable[[T], U], it: Iterator[T],
                 depth: int = 2, join_timeout: float = 5.0,
                 screen: Callable[[T], "str | None"] = None,
                 quarantine=None):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._join_timeout = join_timeout
        self._done = False
        # in-flight window accounting (worker writes, consumer reads;
        # int updates are GIL-atomic enough for bookkeeping)
        self._submitted = 0     # batches handed to put_fn (post-screen)
        self._delivered = 0     # batches the consumer received
        self._screened_out = 0  # batches the screen quarantined

        def put(item) -> bool:
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for item in it:
                    if self._stop.is_set():
                        return
                    if screen is not None:
                        reason = screen(item)
                        if reason is not None:
                            self._screened_out += 1
                            from ..resilience.events import record_event
                            from ..telemetry import global_telemetry
                            global_telemetry().counter(
                                "data/poisoned_batches").inc()
                            record_event(
                                "quarantine", "data.poison",
                                detail=f"pre-upload screen: {reason}")
                            if quarantine is not None:
                                seen = self._submitted + self._screened_out
                                quarantine.note(
                                    "prefetch", f"batch:{seen}", reason)
                            continue
                    self._submitted += 1
                    if not put(put_fn(item)):
                        return
            except BaseException as e:
                from ..resilience.events import record_event
                record_event("pipeline_error", "data.put_batch",
                             detail=f"{type(e).__name__}: {e}")
                put((_SENTINEL, e))
                return
            put((_SENTINEL, None))

        self._thread = threading.Thread(target=worker, daemon=True,
                                        name="flaxdiff-put-batch")
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        got = self._q.get()
        if isinstance(got, tuple) and len(got) == 2 \
                and got[0] is _SENTINEL:
            self._done = True
            if got[1] is not None:
                raise got[1]
            raise StopIteration
        self._delivered += 1
        return got

    def state_dict(self) -> dict:
        """In-flight window snapshot: `submitted - delivered` is the
        number of uploaded-but-unconsumed batches (bounded by
        `depth + 1`); after `close()` it is the discarded window."""
        return {"submitted": self._submitted,
                "delivered": self._delivered,
                "screened_out": self._screened_out,
                "in_flight": self._submitted - self._delivered}

    def close(self) -> None:
        """Stop the worker and join it (bounded). Prefetched-but-unread
        batches are discarded; the source iterator is safe to hand to
        another consumer once this returns with the worker dead."""
        self._stop.set()
        # drain so a worker blocked on a full queue sees the stop flag
        # at its next put poll instead of racing the join below
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        # a post-close next() must fail fast, not block on the drained
        # queue waiting for a worker that is already gone
        self._done = True
        self._thread.join(self._join_timeout)
        if self._thread.is_alive():
            from ..resilience.events import record_event
            record_event("warning", "data.put_batch",
                         detail="upload-prefetch worker did not stop "
                                f"within {self._join_timeout}s (source "
                                "iterator wedged?); it may consume one "
                                "more item before dying")
