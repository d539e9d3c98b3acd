"""Trackers: the one metrics/span interface every layer reports through.

The repo's observability story used to be ad-hoc benchmark prints: each
bench computed its own percentiles and threw the per-query signals away.
This module is the levanter-tracker-shaped abstraction the ROADMAP asked
for — a tiny protocol with three implementations:

  * ``NoopTracker``     — the default everywhere; zero overhead, never syncs;
  * ``InMemoryTracker`` — events held in a list (tests, notebooks);
  * ``JsonlTracker``    — append-only event log on disk, one JSON object per
    line, flushed per event so a crash loses at most the line being written.

Two event kinds flow through a tracker:

  * **metrics** — ``log_metrics({...}, step=...)``: a flat dict of host
    scalars.  Callers convert device values themselves (``int(counter)``,
    ``float(x)``) because *that conversion is a host sync* and the standing
    policy is sync-boundary-only capture: metrics are logged where the code
    already synchronized (after ``block_until_ready``, inside a wave
    callback, after a ``device_get``), never from inside a jitted path.
  * **spans** — ``with tracker.span(name) as sp: ...; sp.sync(out)``:
    wall-clock timing of a scoped operation.  JAX dispatch is async, so a
    span that closes without a sync measures *dispatch*, not device work;
    ``sp.sync(tree)`` calls ``jax.block_until_ready`` on the tree and marks
    the span ``synced`` — the event schema records which kind of time each
    span holds, so a reader never mistakes enqueue time for execution time.
    Under ``NoopTracker`` the ``sync`` is a passthrough (no block): turning
    telemetry OFF must remove every sync it introduced.

Spans nest (a ``serve/step`` span contains an ``index/flush`` span and an
``index/search`` span); the tracker maintains the active-span stack and
stamps each span event with its ``depth`` and ``parent`` so the JSONL
round-trips back into a tree.

Every span, under every tracker ``NoopTracker`` included, is also a
``jax.profiler.TraceAnnotation`` of the same name: a profiler trace of the
process carries the program's spans on its host plane, on the clock of the
device's ops, with the JSONL's vocabulary.  With the profiler off a
TraceAnnotation records nothing.

Trackers never change results: they only read host scalars and timestamps.
``tests/test_obs.py`` pins that searching with a tracker attached is
bit-identical to searching without one (fp32).
"""

from __future__ import annotations

import json
import os
import time
from typing import Iterator, List, Mapping, Optional

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = [
    "Tracker",
    "Span",
    "NoopTracker",
    "InMemoryTracker",
    "JsonlTracker",
    "load_events",
    "span_tree",
]


def _host_scalar(v):
    """Coerce a value to a JSON-able host scalar.

    Accepts python numbers, strings, bools, numpy scalars and 0-d arrays.
    Device arrays reaching this point mean the caller logged from a
    non-sync boundary; ``np.asarray`` will sync them — correct but against
    policy, so keep conversions at the call site.
    """
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    arr = np.asarray(v)
    if arr.ndim == 0:
        return arr.item()
    return arr.tolist()


class Span:
    """One live span: created by ``Tracker.span``, closed by the context
    manager.  ``sync(tree)`` blocks on the tree's device buffers (so the
    elapsed time covers device work, not dispatch) and returns the tree
    unchanged, letting call sites write ``res = sp.sync(res)``."""

    __slots__ = ("name", "t0", "synced", "_tracker")

    def __init__(self, name: str, tracker: "Tracker"):
        self.name = name
        self.t0 = time.perf_counter()
        self.synced = False
        self._tracker = tracker

    def sync(self, tree):
        import jax

        jax.block_until_ready(tree)
        self.synced = True
        return tree


class _NoopSpan:
    """Span stand-in for ``NoopTracker``: no clock read, and — critically —
    ``sync`` does NOT block: telemetry off means no telemetry-introduced
    host syncs anywhere.  ``synced`` accepts (and discards) writes so call
    sites that annotate an existing sync (``sp.synced = True``) need no
    tracker-kind branch."""

    __slots__ = ()
    name = "<noop>"

    @property
    def synced(self) -> bool:
        return False

    @synced.setter
    def synced(self, _v) -> None:
        pass

    def sync(self, tree):
        return tree


_NOOP_SPAN = _NoopSpan()


class Tracker:
    """The protocol + the span-stack machinery shared by real trackers.

    Subclasses implement ``_emit(event: dict)``; everything else —
    ``log_metrics``, the ``span`` context manager, nesting bookkeeping,
    ``finish`` — lives here so the three implementations cannot drift on
    schema.
    """

    def __init__(self):
        self._stack: List[str] = []
        self._t_origin = time.perf_counter()

    # -- subclass surface ----------------------------------------------------

    def _emit(self, event: dict) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    # -- protocol ------------------------------------------------------------

    def log_metrics(
        self, metrics: Mapping[str, object], *, step: Optional[int] = None
    ) -> None:
        """Record a flat dict of host scalars (see module doc for the
        sync-boundary policy).  ``step`` is an optional monotonic ordinal
        (wave index, serving round) for time-series readers."""
        ev = {
            "event": "metrics",
            "t": time.perf_counter() - self._t_origin,
            "metrics": {k: _host_scalar(v) for k, v in metrics.items()},
        }
        if step is not None:
            ev["step"] = int(step)
        if self._stack:
            ev["span"] = self._stack[-1]
        self._emit(ev)

    def span(self, name: str):
        """Context manager timing a scoped operation; yields a ``Span``
        whose ``sync(tree)`` makes the measurement cover device work."""
        return _SpanCtx(self, name)

    def finish(self) -> None:
        """Flush/close; further events are a caller bug (real trackers may
        raise or drop)."""

    # -- internals shared with _SpanCtx --------------------------------------

    def _close_span(self, sp: Span) -> None:
        depth = len(self._stack) - 1
        ev = {
            "event": "span",
            "name": sp.name,
            "t": sp.t0 - self._t_origin,
            "dur_s": time.perf_counter() - sp.t0,
            "depth": depth,
            "synced": sp.synced,
        }
        if depth > 0:
            ev["parent"] = self._stack[depth - 1]
        self._emit(ev)


class _SpanCtx:
    __slots__ = ("_tracker", "_name", "_span", "_annotation")

    def __init__(self, tracker: Tracker, name: str):
        self._tracker = tracker
        self._name = name
        self._span: Optional[Span] = None
        self._annotation = TraceAnnotation(name)

    def __enter__(self) -> Span:
        self._annotation.__enter__()
        self._tracker._stack.append(self._name)
        self._span = Span(self._name, self._tracker)
        return self._span

    def __exit__(self, exc_type, exc, tb):
        try:
            self._tracker._close_span(self._span)
        finally:
            self._tracker._stack.pop()
            self._annotation.__exit__(exc_type, exc, tb)
        return False


class NoopTracker(Tracker):
    """The default: accepts everything, records nothing, syncs nothing.

    ``span`` skips the stack and the clock entirely and emits no event; its
    one cost is the span's ``TraceAnnotation``, which a profiler trace of
    the process records and which is otherwise inert.
    """

    def log_metrics(self, metrics, *, step=None) -> None:
        pass

    def span(self, name: str):
        return _NoopCtx(name)

    def _emit(self, event: dict) -> None:
        pass


class _NoopCtx(TraceAnnotation):
    """The profiler annotation alone; yields the shared ``_NOOP_SPAN``."""

    def __enter__(self):
        super().__enter__()
        return _NOOP_SPAN

#: module-level shared no-op instance — instrumented code uses
#: ``tracker or NOOP`` so the hot path never branches on None twice
NOOP = NoopTracker()


class InMemoryTracker(Tracker):
    """Events in a host list — the test/notebook tracker.

    ``events`` is the raw chronological record; ``metrics_events`` /
    ``span_events`` are filtered views; ``spans(name)`` collects the
    durations of one span name.
    """

    def __init__(self):
        super().__init__()
        self.events: List[dict] = []

    def _emit(self, event: dict) -> None:
        self.events.append(event)

    @property
    def metrics_events(self) -> List[dict]:
        return [e for e in self.events if e["event"] == "metrics"]

    @property
    def span_events(self) -> List[dict]:
        return [e for e in self.events if e["event"] == "span"]

    def spans(self, name: str) -> List[dict]:
        return [e for e in self.span_events if e["name"] == name]


class JsonlTracker(Tracker):
    """Append-only on-disk event log: one JSON object per line.

    Crash-safety contract: the file is opened in append mode and flushed
    (+ fsync'd on ``finish``) per event, so an interrupted run loses at most
    its final partially-written line — and ``load_events`` skips lines that
    fail to parse, so a log with a torn tail still round-trips every
    complete event.  Multiple runs may append to one file; each tracker
    writes a ``run`` header event at open (run metadata: jax/backend
    provenance via ``benchmarks.common``-style dicts or the caller's own),
    so readers can split the log into runs.
    """

    def __init__(self, path: str, run_meta: Optional[dict] = None):
        super().__init__()
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")
        header = {
            "event": "run",
            "wall_time_utc": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
            "pid": os.getpid(),
        }
        if run_meta:
            header["meta"] = {k: _host_scalar(v) for k, v in run_meta.items()}
        self._emit(header)

    def _emit(self, event: dict) -> None:
        if self._f is None:
            return  # post-finish emit: drop rather than crash the host loop
        self._f.write(json.dumps(event, sort_keys=True) + "\n")
        self._f.flush()

    def finish(self) -> None:
        if self._f is not None:
            self._f.flush()
            os.fsync(self._f.fileno())
            self._f.close()
            self._f = None

    def __del__(self):  # best-effort close on GC
        try:
            self.finish()
        except Exception:
            pass


def load_events(path: str) -> List[dict]:
    """Parse a JSONL event log back into event dicts.

    Torn tails (a crash mid-write) and blank lines are skipped, not fatal —
    the crash-safety contract is that every *complete* line round-trips.
    """
    events = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail / partial write
            if isinstance(ev, dict):
                events.append(ev)
    return events


def span_tree(events: List[dict]) -> Iterator[str]:
    """Render span events as an indented tree (depth-stamped at emit time);
    a quick human view of a JSONL log — see docs/observability.md."""
    for e in events:
        if e.get("event") != "span":
            continue
        pad = "  " * int(e.get("depth", 0))
        sync = "" if e.get("synced") else "  [dispatch-only]"
        yield f"{pad}{e['name']}: {e['dur_s'] * 1e3:.2f}ms{sync}"
