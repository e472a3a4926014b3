"""Async serving driver: a background tick loop around
``FederationServer``.

The synchronous server couples solving to the caller — ``submit`` only
enqueues, and nothing completes until someone calls ``tick``/``drain``.
``AsyncDriver`` decouples them: a daemon thread owns the tick loop, so
``submit`` returns a ``ServeFuture`` immediately and the queue drains in
the background at a configurable cadence.

    driver = AsyncDriver(server, interval_s=0.0)
    driver.start()
    futs = [driver.submit(S, ds, seed=0, q=q) for q, (S, ds) in ...]
    driver.wait(futs, timeout_s=60)       # or poll fut.done()
    driver.stop()                         # drains by default, joins

Semantics:

  * DETERMINISM — the driver adds no scheduling of its own: it just
    calls ``server.tick()``, so admission order (deadline → aging →
    fullest bucket, FIFO within bucket) and per-request results are
    IDENTICAL to a manual tick loop over the same submission order
    (padding is provably inert, so results never depend on batch
    composition).  Queue mutations are guarded by the server's lock;
    submits landing mid-tick simply ride the next tick.
  * CADENCE — ``interval_s`` sleeps between NON-EMPTY polls; an empty
    queue parks the thread on a condition variable until the next
    submit (no busy-wait), so an idle driver costs nothing.
  * SHUTDOWN — ``stop(drain=True)`` (default) lets the loop finish the
    queue, then joins the thread; ``stop(drain=False)`` exits after the
    in-flight tick, leaving queued requests pending (the server is
    untouched — a later ``server.drain()`` completes them).
  * METRICS — ``stats()`` reports tick and request counts;
    ``server.metrics`` keeps the solve-side telemetry, and the
    ``serve.tick`` spans (``repro.utils.spans``) time the loop's work
    while a profiler session runs.
"""
from __future__ import annotations

import threading
import time

from repro.serve.queue import FederationServer, ServeFuture


class AsyncDriver:
    """Background tick loop for one ``FederationServer``."""

    def __init__(self, server: FederationServer, interval_s: float = 0.0):
        if interval_s < 0:
            raise ValueError(f"interval_s must be >= 0, got {interval_s}")
        self.server = server
        self.interval_s = float(interval_s)
        self._wake = threading.Condition()
        self._thread = None
        self._running = False
        self._drain_on_stop = True
        self.ticks = 0                   # non-empty ticks fired
        self.empty_polls = 0             # wake-ups that found no work
        self.completed = 0               # requests completed by the loop

    # ------------------------------------------------------------ loop
    def _loop(self):
        while True:
            with self._wake:
                if not self._running:
                    if not (self._drain_on_stop and self.server.pending()):
                        return
                elif not self.server.pending():
                    # park until a submit (or stop) wakes us — no
                    # busy-wait on an idle queue
                    self.empty_polls += 1
                    self._wake.wait(timeout=0.05)
                    continue
            done = self.server.tick()
            if done:
                self.ticks += 1
                self.completed += done
            if self.interval_s and self._running:
                time.sleep(self.interval_s)

    # --------------------------------------------------------- control
    def start(self):
        """Start the background tick thread (idempotent)."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._running = True
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-tick", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout_s: float | None = None):
        """Stop the loop and join the thread.  ``drain=True`` (default)
        finishes the queue first; ``drain=False`` leaves queued requests
        pending on the untouched server."""
        with self._wake:
            self._drain_on_stop = bool(drain)
            self._running = False
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
            if self._thread.is_alive():
                raise TimeoutError(
                    f"serve-tick thread did not stop within {timeout_s}s")
            self._thread = None
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # ---------------------------------------------------------- submit
    def submit(self, S, dataset, *, seed=0, q=0,
               deadline_ticks=None) -> ServeFuture:
        """``server.submit`` + wake the tick thread.  Returns the future
        immediately; the background loop completes it."""
        fut = self.server.submit(S, dataset, seed=seed, q=q,
                                 deadline_ticks=deadline_ticks)
        with self._wake:
            self._wake.notify_all()
        return fut

    @staticmethod
    def wait(futures, timeout_s: float = 60.0, poll_s: float = 0.002):
        """Block until every future is done (or raise ``TimeoutError``)."""
        deadline = time.perf_counter() + timeout_s
        for fut in futures:
            while not fut.done():
                if time.perf_counter() > deadline:
                    raise TimeoutError(
                        "serve futures still pending after "
                        f"{timeout_s}s — is the driver running?")
                time.sleep(poll_s)
        return futures

    # ----------------------------------------------------------- stats
    def stats(self) -> dict:
        """Loop-side counts: non-empty ticks, empty polls, requests
        completed."""
        return {
            "ticks": self.ticks,
            "empty_polls": self.empty_polls,
            "requests_completed": self.completed,
            "interval_s": self.interval_s,
            "running": bool(self._thread is not None
                            and self._thread.is_alive()),
        }
