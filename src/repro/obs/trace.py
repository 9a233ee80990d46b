"""Deterministic trace recording on a virtual clock.

The recorder is the backbone of the observability layer: every span,
point event, dispatch decision and replan decision is appended to a
single ordered record list.  Ordering is given by a *virtual clock* —
a monotonically increasing integer tick bumped once per record plus the
current window index — so two runs of the same deterministic program
produce byte-identical exports.  Wall-clock timings are opt-in
(``wall_clock=True``) and are carried in dedicated ``wall_*`` fields so
exporters can strip them for reproducibility checks.

Design constraints:

* recording must never perturb the computation it observes — the
  recorder only appends to Python lists and bumps counters, and the
  ``NullRecorder`` default makes every hook a no-op attribute access;
* the dispatch hook (:func:`record_dispatch`) is called on *every*
  backend resolution of a scoring, simulator or policy-evaluation sweep,
  so the inactive path is a single module-global ``None`` check;
* this module imports only the standard library (and sibling
  ``repro.obs`` modules), so it can be imported from anywhere in
  ``repro`` without cycles. Once the program has imported ``jax``, every
  span is mirrored onto the profiler's clock as a
  ``jax.profiler.TraceAnnotation`` of the bare span name, so a profiler
  trace names what the host was doing while the device waited.

Per-decision summaries: when an enabled recorder's outermost span closes,
the recorder appends its wall time, the self time of every span name
inside it and the counter deltas made while it was open to a bounded
process-wide buffer, :func:`recent` — a flight recorder of the last
replans' time breakdown.
"""

from __future__ import annotations

import collections
import contextlib
import sys
import time
from dataclasses import dataclass
from typing import Any, Iterator

from repro.obs.metrics import MetricsRegistry, NULL_METRICS

__all__ = [
    "DispatchDecision",
    "NullRecorder",
    "NULL_RECORDER",
    "TraceRecorder",
    "active_recorder",
    "count",
    "recent",
    "record_dispatch",
    "span",
    "unrecorded",
]

# Summaries of the newest outermost spans that :func:`recent` returns.
RECENT_CAPACITY = 16_384
_RECENT: collections.deque[dict[str, Any]] = collections.deque(maxlen=RECENT_CAPACITY)


@dataclass(frozen=True)
class DispatchDecision:
    """One backend resolution of a sweep: closed-form scoring
    (``resolve_closed_form_backend``; regimes shared / per_row / skew),
    ``simulate_batch`` (regime ``simulate``) or ``evaluate_policies_batch``
    (regime ``policy_eval``)."""

    requested: str
    backend: str
    regime: str
    elements: int | None
    n_machines: int | None
    site: str | None
    window: int

    def to_record(self) -> dict[str, Any]:
        return {
            "requested": self.requested,
            "backend": self.backend,
            "regime": self.regime,
            "elements": self.elements,
            "n_machines": self.n_machines,
            "site": self.site,
        }


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` of ``name``, or ``None`` while
    the program has not imported ``jax`` (this module never imports it)."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    return profiler.TraceAnnotation(name)


class _Span:
    """Lightweight span context manager (cheaper than a generator CM).

    The record is emitted at ``__enter__`` (so record order equals
    program order even for nested spans) and its ``dur`` — in virtual
    ticks — is filled in at ``__exit__``.  The object returned by
    ``__enter__`` is the record dict, which the caller may mutate to
    attach result arguments discovered during the span.  The span is
    also timed on ``time.perf_counter_ns`` for the recorder's
    per-decision summaries and mirrored onto the profiler's clock; neither
    touches the record.
    """

    __slots__ = ("_recorder", "_name", "_cat", "_args", "_rec", "_w0", "_ann")

    def __init__(self, recorder: "TraceRecorder", name: str, cat: str,
                 args: dict[str, Any]) -> None:
        self._recorder = recorder
        self._name = name
        self._cat = cat
        self._args = args
        self._rec: dict[str, Any] | None = None
        self._w0 = 0.0
        self._ann = None

    def __enter__(self) -> dict[str, Any]:
        recorder = self._recorder
        rec = recorder._record("span", self._name, self._cat, self._args)
        self._rec = rec
        if recorder.wall_clock:
            self._w0 = time.perf_counter()
        recorder._open(self._name)
        ann = _annotation(self._name)
        if ann is not None:
            ann.__enter__()
            self._ann = ann
        return rec

    def __exit__(self, *exc: Any) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        recorder = self._recorder
        recorder._close()
        rec = self._rec
        recorder._tick += 1
        rec["dur"] = recorder._tick - rec["ts"]
        if recorder.wall_clock:
            rec["wall_dur_s"] = time.perf_counter() - self._w0
        return None


class TraceRecorder:
    """Collects spans, events and decisions on a deterministic virtual clock.

    Parameters
    ----------
    name:
        Label for the run; becomes the process name in Chrome traces and
        the ``meta`` header of JSONL exports.
    wall_clock:
        When ``True``, spans and events additionally carry
        ``wall_s`` / ``wall_dur_s`` fields from ``time.perf_counter``.
        These fields are *never* part of the virtual clock and exporters
        can strip them (``strip_wall=True``) for byte-identical reruns.
    """

    enabled = True

    def __init__(self, name: str = "run", wall_clock: bool = False) -> None:
        self.name = name
        self.wall_clock = wall_clock
        self.records: list[dict[str, Any]] = []
        self.metrics = MetricsRegistry()
        self._tick = 0
        self._window = -1
        self._wall0 = time.perf_counter()
        self._dispatch_counters: dict[tuple[str, str], Any] = {}
        self._dispatch_rows: list[tuple] = []
        self._dispatch_cache: list[DispatchDecision] = []
        # Open spans' [name, start_ns, children_ns], outermost first, and
        # the outermost span's self time per name and counters at entry.
        self._open_spans: list[list] = []
        self._self_ns: dict[str, int] = {}
        self._counters0: dict[str, float] = {}

    # ---------------------------------------------------------------- clock

    @property
    def tick(self) -> int:
        return self._tick

    @property
    def window(self) -> int:
        return self._window

    def set_window(self, window: int) -> None:
        """Advance the virtual clock to a new window index."""
        self._window = int(window)

    # -------------------------------------------------------------- records

    def _record(
        self,
        rtype: str,
        name: str,
        cat: str,
        args: dict[str, Any] | None,
    ) -> dict[str, Any]:
        self._tick += 1
        rec: dict[str, Any] = {
            "type": rtype,
            "name": name,
            "cat": cat,
            "window": self._window,
            "ts": self._tick,
        }
        if args:
            rec["args"] = args
        if self.wall_clock:
            rec["wall_s"] = time.perf_counter() - self._wall0
        self.records.append(rec)
        return rec

    def event(self, name: str, cat: str = "event", **args: Any) -> dict[str, Any]:
        """Record an instantaneous point event."""
        return self._record("event", name, cat, args or None)

    def span(self, name: str, cat: str = "span", **args: Any) -> _Span:
        """Record a nestable span (see :class:`_Span` for semantics)."""
        return _Span(self, name, cat, args)

    def dispatch(
        self,
        requested: str,
        backend: str,
        regime: str,
        elements: int | None,
        n_machines: int | None,
        site: str | None,
    ) -> None:
        """Record one closed-form backend resolution.

        Hot path — called once per scoring sweep during refine.  The
        trace record is a direct dict literal, the per-route counter is
        cached by ``(regime, backend)``, and the :class:`DispatchDecision`
        objects are materialized lazily by the :attr:`dispatch_log`
        property, so the per-call cost is two appends and a counter bump.
        """
        tick = self._tick + 1
        self._tick = tick
        window = self._window
        self._dispatch_rows.append(
            (requested, backend, regime, elements, n_machines, site, window)
        )
        rec: dict[str, Any] = {
            "type": "dispatch",
            "name": "closed_form_dispatch",
            "cat": "dispatch",
            "window": window,
            "ts": tick,
            "args": {
                "requested": requested,
                "backend": backend,
                "regime": regime,
                "elements": None if elements is None else int(elements),
                "n_machines": None if n_machines is None else int(n_machines),
                "site": site,
            },
        }
        if self.wall_clock:
            rec["wall_s"] = time.perf_counter() - self._wall0
        self.records.append(rec)
        ctr = self._dispatch_counters.get((regime, backend))
        if ctr is None:
            ctr = self.metrics.counter(f"dispatch.{regime}.{backend}")
            self._dispatch_counters[(regime, backend)] = ctr
        ctr.add(1)

    @property
    def dispatch_log(self) -> list[DispatchDecision]:
        """All backend resolutions seen so far, as :class:`DispatchDecision`.

        Materialized lazily from the compact rows the hot path appends;
        repeated access only converts rows added since the last call.
        """
        rows = self._dispatch_rows
        cache = self._dispatch_cache
        if len(cache) != len(rows):
            for req, backend, regime, elements, n_machines, site, window in rows[
                len(cache):
            ]:
                cache.append(
                    DispatchDecision(
                        requested=str(req),
                        backend=str(backend),
                        regime=str(regime),
                        elements=None if elements is None else int(elements),
                        n_machines=None if n_machines is None else int(n_machines),
                        site=site,
                        window=window,
                    )
                )
        return cache

    def decision(self, dec: Any) -> None:
        """Record a structured replan decision (``repro.obs.ledger.ReplanDecision``)."""
        self._record("decision", f"replan:{dec.outcome}", "decision", dec.to_record())

    # ------------------------------------------------------ wall summaries

    def _counter_values(self) -> dict[str, float]:
        return {m.name: m.value for m in self.metrics if m.kind == "counter"}

    def _open(self, name: str) -> None:
        if not self._open_spans:
            self._self_ns = {}
            self._counters0 = self._counter_values()
        self._open_spans.append([name, time.perf_counter_ns(), 0])

    def _close(self) -> None:
        name, t0, children = self._open_spans.pop()
        wall = time.perf_counter_ns() - t0
        self._self_ns[name] = self._self_ns.get(name, 0) + wall - children
        if self._open_spans:
            self._open_spans[-1][2] += wall
            return
        c0 = self._counters0
        _RECENT.append({
            "name": name,
            "wall_s": wall * 1e-9,
            "self_s": {k: v * 1e-9 for k, v in self._self_ns.items()},
            "counters": {
                k: v - c0.get(k, 0.0)
                for k, v in self._counter_values().items()
                if v != c0.get(k, 0.0)
            },
        })

    # ------------------------------------------------------------ activation

    def activate(self) -> contextlib.AbstractContextManager["TraceRecorder"]:
        """Install this recorder as the process-wide active recorder.

        The active recorder is the target of :func:`record_dispatch`,
        which instruments code (the closed-form backend resolver) too far
        from the call site to thread a recorder argument through.
        Activation nests: the previous active recorder is restored on
        exit.
        """
        return _activate(self)


@contextlib.contextmanager
def _activate(rec: TraceRecorder | None) -> Iterator[TraceRecorder | None]:
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = rec
    try:
        yield rec
    finally:
        _ACTIVE = prev


_ACTIVE: TraceRecorder | None = None


def active_recorder() -> TraceRecorder | None:
    """The currently activated :class:`TraceRecorder`, or ``None``."""
    return _ACTIVE


class _NullContext:
    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc: Any) -> None:
        return None


_NULL_CTX = _NullContext()


def span(name: str, cat: str = "span") -> _Span | _NullContext:
    """A span named ``name`` on the active recorder — for code too far
    from the caller to thread a recorder through (candidate building,
    scoring sweeps, transfers). With no active recorder: one global read
    and the shared null context."""
    rec = _ACTIVE
    if rec is None:
        return _NULL_CTX
    return rec.span(name, cat)


def count(name: str, amount: float) -> None:
    """Add ``amount`` to the active recorder's counter ``name``; nothing
    when no recorder is active."""
    rec = _ACTIVE
    if rec is None:
        return
    rec.metrics.counter(name).add(amount)


def unrecorded() -> contextlib.AbstractContextManager[None]:
    """No recorder is active inside: dispatch decisions, spans and counters
    made there reach none. For a resolution made only to look ahead, which
    the sweep that follows makes again on the record."""
    return _activate(None)


def recent() -> list[dict[str, Any]]:
    """Summaries of the newest outermost spans of enabled recorders, oldest
    first, at most ``RECENT_CAPACITY``: ``{"name", "wall_s", "self_s":
    {span name: seconds inside it and in none of its children},
    "counters": {counter: nonzero delta while the span was open}}``. Self
    times sum to ``wall_s`` (timed in integer nanoseconds)."""
    return list(_RECENT)


def record_dispatch(
    requested: str,
    backend: str,
    regime: str,
    elements: int | None,
    n_machines: int | None,
    site: str | None = None,
) -> None:
    """Dispatch-decision hook called by ``resolve_closed_form_backend``,
    ``simulate_batch`` and ``evaluate_policies_batch``.

    A single global read when no recorder is active, so the instrumented
    resolver costs nothing in normal operation.
    """
    rec = _ACTIVE
    if rec is None:
        return
    rec.dispatch(requested, backend, regime, elements, n_machines, site)


class NullRecorder:
    """Zero-overhead recorder: every hook is a no-op.

    Shared singleton :data:`NULL_RECORDER` is the default everywhere a
    recorder is accepted, so un-instrumented runs pay only ``enabled``
    attribute checks.
    """

    enabled = False
    wall_clock = False
    name = "null"
    records: list[dict[str, Any]] = []
    dispatch_log: list[DispatchDecision] = []
    metrics = NULL_METRICS
    tick = 0
    window = -1

    def set_window(self, window: int) -> None:
        return None

    def event(self, name: str, cat: str = "event", **args: Any) -> None:
        return None

    def span(self, name: str, cat: str = "span", **args: Any) -> _NullContext:
        return _NULL_CTX

    def dispatch(self, *args: Any, **kwargs: Any) -> None:
        return None

    def decision(self, dec: Any) -> None:
        return None

    def activate(self) -> _NullContext:
        return _NULL_CTX


NULL_RECORDER = NullRecorder()
