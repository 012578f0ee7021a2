"""Module runtime: one thread + one asyncio event loop per module.

Functional equivalent of the reference's OpenrEventBase
(openr/common/OpenrEventBase.h:28) — every framework module extends this and
runs in its own thread (reference: startEventBase, openr/Main.cpp:132-163).
Fibers become asyncio tasks; timers become loop timers; the health timestamp
feeds the Watchdog exactly like getTimestamp() (OpenrEventBase.h:74).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import threading
import time
from typing import Any, Awaitable, Callable, Coroutine, Optional

from ..analysis import race as _race
from ..analysis import sched as _sched
from ..obs import trace as _trace

log = logging.getLogger(__name__)


def _tsan_handoff(fn: Callable[..., Any]) -> Callable[..., Any]:
    """OPENR_TSAN: wrap a closure about to be marshalled to another thread
    (call_soon_threadsafe and friends) with a happens-before handoff edge.
    Identity when disarmed — a single module-attribute load."""
    det = _race.TSAN
    return fn if det is None else det.wrap_handoff(fn)


def _obs_handoff(fn: Callable[..., Any], loop: str) -> Callable[..., Any]:
    """OPENR_TRACE: carry the caller's active span scope across the same
    thread handoff, so work marshalled onto a module loop keeps its
    trace attribution (and records its wait for `loop`).  Identity when
    disarmed (one attribute load) or when the caller has no active
    scope."""
    tr = _trace.TRACE
    return fn if tr is None else tr.bind_scope(fn, loop)


def _sched_submit(eb: "OpenrEventBase") -> None:
    """OPENR_SCHED: a cross-thread submit (run_in_event_base_thread /
    add_fiber_task / schedule_timeout marshalling) is a yield point for
    controlled tasks.  One module-attribute load when disarmed."""
    sc = _sched.SCHED
    if sc is not None:
        sc.handoff(eb)


def _handoff(fn: Callable[..., Any], loop: str) -> Callable[..., Any]:
    """Compose the cross-thread wrappers for a closure bound for the
    module loop named `loop` (trace innermost so the TSAN handoff edge
    brackets the whole marshalled closure)."""
    return _tsan_handoff(_obs_handoff(fn, loop))


class Timeout:
    """Cancellable cross-thread timer token returned by
    OpenrEventBase.schedule_timeout."""

    def __init__(self) -> None:
        self._handle: Optional[asyncio.TimerHandle] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._cancelled = False
        self._lock = threading.Lock()

    def _arm(
        self, loop: asyncio.AbstractEventLoop, delay_s: float, fn: Callable[[], Any]
    ) -> None:
        with self._lock:
            if self._cancelled:
                return
            self._loop = loop
            self._handle = loop.call_later(delay_s, fn)

    def cancel(self) -> None:
        """Cancel from any thread.  If the timer already fired, this is a
        no-op (cross-thread cancellation is inherently racy; callbacks should
        tolerate one late firing)."""
        with self._lock:
            self._cancelled = True
            handle, loop = self._handle, self._loop
            self._handle = None
        if handle is not None and loop is not None:
            try:
                loop.call_soon_threadsafe(handle.cancel)
            except RuntimeError:
                pass  # loop closed


class OpenrEventBase:
    def __init__(self, name: str = "") -> None:
        self.name = name or type(self).__name__
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._started = threading.Event()
        self._stopped = threading.Event()
        self._stop_once = threading.Lock()
        self._stop_called = False
        self._tasks: set[asyncio.Task] = set()
        self._timestamp = time.monotonic()

    # -- lifecycle ----------------------------------------------------------

    def run(self) -> None:
        """Start the module thread and event loop; returns once running."""
        if self._thread is not None:
            raise RuntimeError(f"{self.name} already started")
        self._thread = threading.Thread(target=self._thread_main, name=self.name)
        self._thread.daemon = True
        self._thread.start()
        self._started.wait()

    def _thread_main(self) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop
        try:
            try:
                self._track(
                    loop.create_task(self._heartbeat(), name=f"{self.name}-heartbeat")
                )
                init = getattr(self, "prepare", None)
                if init is not None:
                    task = loop.create_task(init(), name=f"{self.name}-prepare")
                    self._track(task)
            finally:
                # never leave run() parked on _started if startup raised
                self._started.set()
            loop.run_forever()
            # drain: cancel outstanding tasks
            for task in list(self._tasks):
                task.cancel()
            pending = [t for t in self._tasks if not t.done()]
            if pending:
                loop.run_until_complete(
                    asyncio.gather(*pending, return_exceptions=True)
                )
        finally:
            loop.close()
            self._stopped.set()

    async def _heartbeat(self) -> None:
        while True:
            self._timestamp = time.monotonic()
            await asyncio.sleep(0.1)

    def stop(self) -> None:
        """Stop the loop and join the thread (callable from any thread;
        idempotent — later callers just wait for the first stop to finish)."""
        if self._loop is None:
            return
        with self._stop_once:
            first = not self._stop_called
            self._stop_called = True
        if not first:
            if threading.current_thread() is not self._thread:
                self.wait_until_stopped()
            return
        stopping = getattr(self, "stopping", None)

        def _do_stop() -> None:
            async def _graceful():
                if stopping is not None:
                    try:
                        await stopping()
                    except Exception:
                        log.exception("%s: stopping() hook failed", self.name)
                self._loop.stop()

            self._loop.create_task(_graceful())

        try:
            self._loop.call_soon_threadsafe(_handoff(_do_stop, self.name))
        except RuntimeError:
            return
        # Joining from the module's own loop thread would deadlock (the loop
        # must keep running to execute _do_stop); the stop is then async.
        if threading.current_thread() is not self._thread:
            self.wait_until_stopped()

    def wait_until_running(self, timeout: Optional[float] = None) -> bool:
        return self._started.wait(timeout)

    def wait_until_stopped(self, timeout: Optional[float] = None) -> bool:
        if self._thread is None:
            return True  # never started (e.g. startup aborted mid-way)
        ok = self._stopped.wait(timeout)
        if ok:
            self._thread.join()
        return ok

    @property
    def is_running(self) -> bool:
        return self._started.is_set() and not self._stopped.is_set()

    # -- task / timer API (reference: addFiberTask :47, scheduleTimeout) ----

    def _track(self, task: asyncio.Task) -> None:
        self._tasks.add(task)

        def _done(t: asyncio.Task) -> None:
            self._tasks.discard(t)
            if not t.cancelled():
                exc = t.exception()
                if exc is not None and not isinstance(exc, asyncio.CancelledError):
                    log.exception(
                        "%s: task %s crashed", self.name, t.get_name(), exc_info=exc
                    )

        task.add_done_callback(_done)

    def add_fiber_task(self, coro: Coroutine[Any, Any, Any], name: str = "") -> None:
        """Schedule a long-running coroutine on this module's loop (from any
        thread). Reference: addFiberTask, OpenrEventBase.h:47."""
        assert self._loop is not None, f"{self.name} not started"
        _sched_submit(self)

        def _create() -> None:
            self._track(self._loop.create_task(coro, name=name or "fiber"))

        self._loop.call_soon_threadsafe(_handoff(_create, self.name))

    def in_event_base_thread(self) -> bool:
        return threading.current_thread() is self._thread

    def run_in_event_base_thread(
        self, fn: Callable[[], Any]
    ) -> "concurrent.futures.Future[Any]":
        """Marshal a call onto this module's thread and return a future for
        the result.  Reference pattern: runInEventBaseThread + SemiFuture
        (openr/decision/Decision.cpp:1513) — the cross-thread RPC mechanism.
        Re-entrant: from the owning thread the call runs inline (blocking on
        the future there would deadlock the loop)."""
        assert self._loop is not None, f"{self.name} not started"
        _sched_submit(self)
        fut: concurrent.futures.Future = concurrent.futures.Future()
        if self.in_event_base_thread():
            try:
                fut.set_result(fn())
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)
            return fut

        def _call() -> None:
            if not fut.set_running_or_notify_cancel():
                return
            try:
                fut.set_result(fn())
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        self._loop.call_soon_threadsafe(_handoff(_call, self.name))
        return fut

    async def run_async(self, coro: Awaitable[Any]) -> Any:
        """Await a coroutine on this module's loop from another loop/thread."""
        return await asyncio.wrap_future(self.run_coroutine(coro))

    def run_coroutine(self, coro: Awaitable[Any]) -> "concurrent.futures.Future[Any]":
        assert self._loop is not None
        det = _race.TSAN
        if det is not None:
            # forward edge: caller -> coroutine body on the module loop.
            # The return edge needs no wrap — wrap_future/result() observe
            # the patched Future resolve token.
            coro = det.wrap_coro(coro)
        return asyncio.run_coroutine_threadsafe(coro, self._loop)

    def schedule_timeout(self, delay_s: float, fn: Callable[[], Any]) -> "Timeout":
        """Schedule fn after delay on this module's loop; returns a
        cancellable token (Spark-style hold timers reset constantly)."""
        assert self._loop is not None
        _sched_submit(self)
        token = Timeout()
        self._loop.call_soon_threadsafe(
            _handoff(token._arm, self.name),
            self._loop,
            delay_s,
            _handoff(fn, self.name),
        )
        return token

    # -- watchdog interface (reference: getTimestamp, OpenrEventBase.h:74) --

    def get_timestamp(self) -> float:
        return self._timestamp
