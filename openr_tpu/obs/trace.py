"""End-to-end span tracing across queues, modules, and engine rungs.

Arming discipline (mirrors ``analysis/race.py`` OPENR_TSAN exactly):

- ``TRACE`` is a module-level constant, ``None`` unless armed.  Every
  seam in the tree reads it LATE-BOUND (``_trace.TRACE``, never
  ``from ... import TRACE``) and guards with a single
  ``if tr is not None`` — an attribute load per seam when off, no
  wrappers installed, no tokens allocated.
- ``OPENR_TRACE=1`` arms at import; ``OPENR_TRACE_SAMPLE=N`` keeps one
  in N roots (deterministic modulo counter, NOT random — the
  determinism contract below depends on it); ``OPENR_TRACE_RING=N``
  bounds completed-trace storage.
- Tests arm/disarm explicitly via :func:`enable` / :func:`disable`.

Span model: a trace is born at an entry point (serving query submit,
KvStore publication, Spark neighbor event) as a *root* span and flows
through the existing concurrency seams — RWQueue put→get carries the
active scope positionally next to the item (the ``_tsan_tokens``
pattern), OpenrEventBase handoffs re-activate the captured scope on the
loop thread, and batch execution activates EVERY coalesced query's span
at once so one engine annotation lands on each (fan-in scope).

Profiler clock: every LIVE span (:meth:`Tracer.child` and
:meth:`Tracer.fan_in`) also runs its body inside one
``jax.profiler.TraceAnnotation`` of the same name, so it lands on the
thread's line of a profiler trace's ``/host:CPU`` plane, on the clock
the device ops use.  Retroactive stages (:meth:`Tracer.stage`) stay
host-clock only.

Determinism contract: :meth:`Span.structure` serializes ONLY stage
names, structural tags (engine rung, dispatch kind, outcome), and the
child set — children sorted lexicographically, timers and ``note``
metadata excluded — so same-seed chaos replays produce byte-identical
structures and the fuzzer can ingest them as coverage tokens.

This module never imports jax (or anything heavier than stdlib): the
annotation is looked up in ``sys.modules`` when a live span opens.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence

# Pre-seeded registry (analysis: counter-unbumped checks seeds vs bumps).
OBS_COUNTER_KEYS = (
    "obs.traces_started",
    "obs.traces_sampled_out",
    "obs.traces_finished",
    "obs.spans_total",
    "obs.trace_ring_evictions",
)


def _now_us() -> int:
    return time.perf_counter_ns() // 1_000


_NULL = nullcontext()


def _annotation(name: str):
    """The profiler's host span for a live span: a
    ``jax.profiler.TraceAnnotation`` once jax is loaded (a daemon armed
    before jax loads still annotates later spans), else a no-op."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    return _NULL if profiler is None else profiler.TraceAnnotation(name)


class Span:
    """One stage of one traced request.

    ``tags`` are STRUCTURAL (part of the determinism contract:
    stages, rungs, retry/hedge edges); ``notes`` are informational
    (sizes, epochs, timings) and excluded from :meth:`structure`.
    Mutations go through the tracer's lock: spans cross threads
    (submit thread → eventbase → executor → reply thread) and a hedged
    call can have two replicas annotating the same span concurrently.
    """

    __slots__ = (
        "name",
        "parent",
        "children",
        "tags",
        "notes",
        "t_start_us",
        "t_end_us",
    )

    def __init__(self, name: str, parent: Optional["Span"] = None) -> None:
        self.name = name
        self.parent = parent
        self.children: list[Span] = []
        self.tags: dict[str, Any] = {}
        self.notes: dict[str, Any] = {}
        self.t_start_us = _now_us()
        self.t_end_us: Optional[int] = None

    # -- mutation (armed paths only; guarded by Tracer._lock) ---------------

    def root(self) -> "Span":
        sp = self
        while sp.parent is not None:
            sp = sp.parent
        return sp

    def finish(self) -> None:
        if self.t_end_us is None:
            self.t_end_us = _now_us()

    # -- canonical structure (the determinism contract) ---------------------

    def structure(self) -> str:
        tags = ",".join(f"{k}={v}" for k, v in sorted(self.tags.items()))
        kids = ",".join(sorted(c.structure() for c in self.children))
        return f"{self.name}({tags})[{kids}]"

    def to_dict(self, t0_us: Optional[int] = None) -> dict:
        """JSON-able tree with timings relative to the root start."""
        base = self.t_start_us if t0_us is None else t0_us
        end = self.t_end_us
        return {
            "name": self.name,
            "t_offset_us": self.t_start_us - base,
            "duration_us": None if end is None else end - self.t_start_us,
            "tags": dict(self.tags),
            "notes": dict(self.notes),
            "children": [c.to_dict(base) for c in self.children],
        }


class Tracer:
    """Span factory + thread-local scope stack + bounded trace ring."""

    def __init__(self, sample_every: int = 1, ring: int = 256) -> None:
        self.sample_every = max(1, int(sample_every))
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ring: deque[Span] = deque(maxlen=max(1, int(ring)))
        self._structure_tokens: set[str] = set()
        self._n_roots = 0
        self._counters: dict[str, int] = {k: 0 for k in OBS_COUNTER_KEYS}

    # -- scope (thread-local) -----------------------------------------------

    def scope(self) -> tuple:
        return getattr(self._tls, "scope", ())

    @contextmanager
    def activate(self, spans: Sequence[Span]) -> Iterator[None]:
        """Make `spans` the current scope on this thread (replaces, does
        not nest-merge: a queue hop or batch activation IS the new
        attribution set)."""
        prev = getattr(self._tls, "scope", ())
        self._tls.scope = tuple(spans)
        try:
            yield
        finally:
            self._tls.scope = prev

    def bind_scope(
        self, fn: Callable[..., Any], loop: str = ""
    ) -> Callable[..., Any]:
        """Capture the current scope for a closure about to be marshalled
        to another thread (eventbase handoffs), and record the time from
        the handoff to the closure's start as an ``eventbase.wait`` stage
        tagged with the receiving `loop`.  Identity when there is nothing
        to carry."""
        scope = self.scope()
        if not scope:
            return fn
        t_handoff = _now_us()

        def _with_scope(*args: Any, **kwargs: Any) -> Any:
            # the closure's wait for the loop thread, on every carried span
            t_run = _now_us()
            for sp in scope:
                self.stage(sp, "eventbase.wait", t_handoff, t_run, loop=loop)
            with self.activate(scope):
                return fn(*args, **kwargs)

        return _with_scope

    # -- span creation ------------------------------------------------------

    def root(self, name: str, **tags: Any) -> Optional[Span]:
        """Trace-context birth at an entry point.  If a scope is already
        active (e.g. router → scheduler submit on the same thread) the
        trace EXTENDS instead: the new span is a child of the first
        active span.  True roots are sampled 1-in-``sample_every`` with
        a deterministic modulo counter."""
        scope = self.scope()
        if scope:
            return self.child_open(scope[0], name, **tags)
        with self._lock:
            self._n_roots += 1
            if (self._n_roots - 1) % self.sample_every:
                self._counters["obs.traces_sampled_out"] += 1
                return None
            self._counters["obs.traces_started"] += 1
            self._counters["obs.spans_total"] += 1
        sp = Span(name)
        sp.tags.update(tags)
        return sp

    def child_open(self, parent: Span, name: str, **tags: Any) -> Span:
        """Open (unfinished) child span; caller finishes it."""
        sp = Span(name, parent=parent)
        sp.tags.update(tags)
        with self._lock:
            parent.children.append(sp)
            self._counters["obs.spans_total"] += 1
        return sp

    @contextmanager
    def child(self, name: str, **tags: Any) -> Iterator[None]:
        """Completed child under EVERY span in the current scope; the
        children become the scope for the duration (so nested seams
        attribute under the stage, not beside it)."""
        scope = self.scope()
        if not scope:
            yield
            return
        with self.fan_in(scope, name, **tags):
            yield

    @contextmanager
    def fan_in(
        self, parents: Iterable[Span], name: str, **tags: Any
    ) -> Iterator[list]:
        """Open a child `name` under each of `parents` (duplicates
        folded), make the children the scope for the body, and finish
        them on exit, raised or not.  The body runs inside ONE profiler
        annotation `name`, however many parents fan in."""
        kids = [
            self.child_open(sp, name, **tags) for sp in dict.fromkeys(parents)
        ]
        try:
            with _annotation(name), self.activate(kids):
                yield kids
        finally:
            now = _now_us()
            for k in kids:
                if k.t_end_us is None:
                    k.t_end_us = now

    def stage(
        self, span: Span, name: str, t0_us: int, t1_us: int, **tags: Any
    ) -> Span:
        """Append a completed child with explicit bounds (used when a
        stage's start was only timestamped, e.g. admission → drain)."""
        sp = Span(name, parent=span)
        sp.tags.update(tags)
        sp.t_start_us = t0_us
        sp.t_end_us = t1_us
        with self._lock:
            span.children.append(sp)
            self._counters["obs.spans_total"] += 1
        return sp

    def event(self, name: str, **tags: Any) -> None:
        """Zero-duration structural edge (retry, hedge, failover) on
        every span in the current scope."""
        now = _now_us()
        for sp in self.scope():
            ev = Span(name, parent=sp)
            ev.tags.update(tags)
            ev.t_start_us = ev.t_end_us = now
            with self._lock:
                sp.children.append(ev)
                self._counters["obs.spans_total"] += 1

    def annotate(self, key: str, value: Any) -> None:
        """Structural tag on every span in the current scope (engine
        rung attribution rides this)."""
        for sp in self.scope():
            with self._lock:
                sp.tags[key] = value

    def note(self, key: str, value: Any) -> None:
        """Non-structural metadata (sizes, epochs); excluded from
        :meth:`Span.structure`."""
        for sp in self.scope():
            with self._lock:
                sp.notes[key] = value

    # -- queue carry (put→get token, the _tsan_tokens pattern) --------------

    def carry(self) -> Optional[tuple]:
        """Token stored positionally next to a queued item at push."""
        scope = self.scope()
        return scope or None

    def set_carried(self, token: tuple) -> None:
        """Queue pop side: stash the popped token; the consumer adopts
        it with :meth:`take_carried` immediately after get() returns
        (same thread, no interleave before the adoption point)."""
        self._tls.carried = token

    def take_carried(self) -> tuple:
        tok = getattr(self._tls, "carried", None)
        self._tls.carried = None
        return tok or ()

    # -- completion ---------------------------------------------------------

    def finish(self, span: Span) -> None:
        """Finish a span; a ROOT lands in the bounded ring and its
        canonical structure joins the fuzzer-facing token set."""
        span.finish()
        if span.parent is not None:
            return
        with self._lock:
            self._counters["obs.traces_finished"] += 1
            if len(self._ring) == self._ring.maxlen:
                self._counters["obs.trace_ring_evictions"] += 1
            self._ring.append(span)
            self._structure_tokens.add(span.structure())

    def finish_root(self, span: Span) -> None:
        """Finish the ROOT of a carried span exactly once (terminal
        seams: reply delivered, Fib programmed)."""
        root = span.root()
        already = root.t_end_us is not None
        if not already:
            self.finish(root)

    # -- export -------------------------------------------------------------

    def dump(self, n: int = 16) -> list[dict]:
        with self._lock:
            recent = list(self._ring)[-max(0, int(n)):]
        return [sp.to_dict() for sp in recent]

    def span_samples(self, n: int = 32) -> list[dict]:
        """Recent traces grouped by canonical structure, with counts and
        duration attribution per distinct shape."""
        with self._lock:
            recent = list(self._ring)
        groups: dict[str, dict] = {}
        for sp in recent:
            key = sp.structure()
            g = groups.get(key)
            dur = (sp.t_end_us or sp.t_start_us) - sp.t_start_us
            if g is None:
                groups[key] = {"structure": key, "count": 1, "max_us": dur}
            else:
                g["count"] += 1
                g["max_us"] = max(g["max_us"], dur)
        out = sorted(groups.values(), key=lambda g: -g["count"])
        return out[: max(0, int(n))]

    def drain_structure_tokens(self) -> frozenset:
        """Pop the accumulated canonical-structure set (fuzzer coverage
        fingerprint ingestion; each run drains its own tokens)."""
        with self._lock:
            toks, self._structure_tokens = frozenset(self._structure_tokens), set()
        return toks

    def get_counters(self) -> dict[str, int]:
        with self._lock:
            return dict(self._counters)


class ObsStats:
    """The ctrl handler's ``obs`` surface.  Reads ``TRACE`` late-bound so
    the daemon dumps zeroed ``obs.*`` counters (and empty trace lists)
    when tracing is unarmed — the wire shape is arming-independent."""

    def get_counters(self) -> dict[str, int]:
        tr = TRACE
        if tr is None:
            return {k: 0 for k in OBS_COUNTER_KEYS}
        return tr.get_counters()

    def dump_traces(self, n: int = 16) -> list[dict]:
        tr = TRACE
        return [] if tr is None else tr.dump(n)

    def span_samples(self, n: int = 32) -> list[dict]:
        tr = TRACE
        return [] if tr is None else tr.span_samples(n)


# -- arming ------------------------------------------------------------------

TRACE: Optional[Tracer] = None


def maybe_child(name: str, **tags: Any):
    """Seam helper for cold paths: a completed child under the current
    scope when armed, a shared no-op context when off (one module
    function call; hot paths use the explicit ``if tr is not None``
    guard instead)."""
    tr = TRACE
    return _NULL if tr is None else tr.child(name, **tags)


def enable(sample_every: int = 1, ring: int = 256) -> Tracer:
    """Arm tracing (tests, bench, ops).  Returns the installed tracer."""
    global TRACE
    TRACE = Tracer(sample_every=sample_every, ring=ring)
    return TRACE


def disable() -> None:
    global TRACE
    TRACE = None


def maybe_enable() -> Optional[Tracer]:
    """Arm from the environment (OPENR_TRACE=1); no-op when already
    armed or unrequested."""
    if TRACE is not None:
        return TRACE
    if os.environ.get("OPENR_TRACE", "") != "1":
        return None
    return enable(
        sample_every=int(os.environ.get("OPENR_TRACE_SAMPLE", "1")),
        ring=int(os.environ.get("OPENR_TRACE_RING", "256")),
    )


maybe_enable()
