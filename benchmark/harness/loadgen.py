"""The benchmark's own traffic generation for the serving kinds: the
dealt request sequence, the closed-loop clients and the open-loop
arrival clock (Poisson, ramp and burst shapes, after the program's
`serving/loadgen.py`, kept here so that a later PR can change the
program's generator and not the yardstick's)."""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import weights


def dealt_nfe(seed: int, deal: Dict[str, int], n: int) -> List[int]:
    """NFE of the first `n` requests: every block holds exactly the
    deal's counts, in seeded order, so that the mean work per request
    does not wander with the seed."""
    rng = np.random.default_rng([weights.seed32(seed), 15485863])
    block = [int(k) for k, c in deal.items() for _ in range(int(c))]
    out: List[int] = []
    while len(out) < n:
        out.extend(int(v) for v in rng.permutation(block))
    return out[:n]


def arrivals(seed: int, n: int, rate_hz: float, shape: str = "poisson",
             peak_factor: float = 1.0, burst_len: int = 1,
             burst_idle_s: float = 0.0) -> List[float]:
    """Due times (seconds from the window's start) of `n` requests."""
    if shape not in ("poisson", "ramp", "burst"):
        raise ValueError(f"unknown arrival shape {shape!r}")
    rng = np.random.default_rng([weights.seed32(seed), 32452843])
    out, clock = [], 0.0
    for k in range(n):
        if shape == "ramp":
            frac = k / max(1, n - 1)
            rate = rate_hz * (1.0 + (peak_factor - 1.0)
                              * math.sin(math.pi * frac) ** 2)
            clock += float(rng.exponential(1.0 / rate))
        elif shape == "burst":
            if k and k % max(1, burst_len) == 0:
                clock += burst_idle_s
            clock += float(rng.exponential(1.0 / (rate_hz * peak_factor)))
        else:
            clock += float(rng.exponential(1.0 / rate_hz))
        out.append(clock)
    return out


@dataclasses.dataclass
class Done:
    index: int
    fields: Dict[str, Any]       # what the request was made from
    result: Any                  # SampleResult, or None
    error: Optional[str]
    due_t: float                 # when it was due (open loop) / submitted
    sent_t: float
    done_t: float

    @property
    def latency_ms(self) -> float:
        return (self.done_t - self.due_t) * 1e3


def row_turns_per_s(done: List["Done"]) -> float:
    """Row-turns completed a second, between completions: with the
    requests that completed in order of `done_t`, d_1 ... d_n, and
    `turns(d) = images x (nfe + 1)`,

        sum_{i = 2..n} turns(d_i) / (done_t(d_n) - done_t(d_1)).

    A row's trajectory is its `nfe` sampler turns and one terminal
    denoise, which rides the round as a turn like any other: the
    program counts all `nfe + 1` in `serving/row_steps_live`, and the
    terminal ones once more, apart, in `serving/terminal_turns`. Both
    edges of the interval are completions, so no edge cuts a request:
    where requests are served one after another the rate is exact, and
    where a round pools rows the requests in flight at the two edges
    cancel in expectation. It reads `Done.done_t` and `Done.fields`
    and nothing the program made. Fewer than three completions, or
    none apart in time, are not a rate: ValueError."""
    ds = sorted(done, key=lambda d: d.done_t)
    span = ds[-1].done_t - ds[0].done_t if ds else 0.0
    if len(ds) < 3 or not span > 0:
        raise ValueError(
            f"{len(ds)} completion(s) over {span:.3f} s are not a rate of "
            "row-turns: three or more, apart in time, are")
    return sum(int(d.fields["images"]) * (int(d.fields["nfe"]) + 1)
               for d in ds[1:]) / span


class Recorder:
    def __init__(self):
        self.lock = threading.Lock()
        self.done: List[Done] = []

    def add(self, d: Done) -> None:
        with self.lock:
            self.done.append(d)

    def snapshot(self) -> List[Done]:
        with self.lock:
            return list(self.done)


def closed_loop(submit: Callable[[int], Any], clients: int,
                stop: threading.Event, rec: Recorder,
                fields_of: Callable[[int], Dict[str, Any]],
                timeout_s: float = 600.0) -> List[threading.Thread]:
    """Start `clients` threads; each takes the next request index,
    submits it, waits for the result, and goes on until `stop`."""
    counter = {"next": 0}
    lock = threading.Lock()

    def client():
        while not stop.is_set():
            with lock:
                i = counter["next"]
                counter["next"] += 1
            t0 = time.perf_counter()
            res, err = None, None
            try:
                res = submit(i).result(timeout=timeout_s)
            except Exception as e:  # noqa: BLE001 - counted as failed
                err = f"{type(e).__name__}: {e}"
            rec.add(Done(i, fields_of(i), res, err, t0, t0,
                         time.perf_counter()))

    threads = [threading.Thread(target=client, name=f"client-{c}",
                                daemon=True) for c in range(clients)]
    for t in threads:
        t.start()
    return threads


def open_loop(submit: Callable[[int], Any], due: List[float], t0: float,
              stop: threading.Event, rec: Recorder,
              fields_of: Callable[[int], Dict[str, Any]], workers: int = 2,
              timeout_s: float = 600.0) -> List[threading.Thread]:
    """Submit request k when it is due (`t0 + due[k]`), whether or not
    earlier ones have finished; a request is timed from when it was due.
    The generator's own lateness is `sent_t - due_t`."""
    waiters: List[threading.Thread] = []

    def wait_for(i, fut, due_t, sent_t):
        res, err = None, None
        try:
            res = fut.result(timeout=timeout_s)
        except Exception as e:  # noqa: BLE001 - counted as failed
            err = f"{type(e).__name__}: {e}"
        rec.add(Done(i, fields_of(i), res, err, due_t, sent_t,
                     time.perf_counter()))

    def submitter(offset):
        for i in range(offset, len(due), workers):
            due_t = t0 + due[i]
            delay = due_t - time.perf_counter()
            if delay > 0 and stop.wait(delay):
                return
            if stop.is_set():
                return
            sent = time.perf_counter()
            try:
                fut = submit(i)
            except Exception as e:  # noqa: BLE001 - shed at the door
                rec.add(Done(i, fields_of(i), None,
                             f"{type(e).__name__}: {e}", due_t, sent, sent))
                continue
            w = threading.Thread(target=wait_for,
                                 args=(i, fut, due_t, sent), daemon=True)
            w.start()
            waiters.append(w)

    threads = [threading.Thread(target=submitter, args=(o,), daemon=True)
               for o in range(workers)]
    for t in threads:
        t.start()
    threads.append(_Joiner(threads[:], waiters))
    return threads


class _Joiner:
    """Joins the submitters first, then every waiter they started."""

    def __init__(self, submitters, waiters):
        self.submitters, self.waiters = submitters, waiters

    def join(self, timeout=None):
        for t in self.submitters:
            t.join(timeout)
        for w in list(self.waiters):
            w.join(timeout)

    def is_alive(self):
        return any(t.is_alive() for t in self.submitters + self.waiters)
