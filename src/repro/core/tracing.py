"""Spans and counters of the served path, on the host clock and, in a
profile taken at the same time, on the device trace's.

The tracer is off by default. Off, :func:`span` returns one shared no-op
context manager and :func:`count` is a flag check: the serving step
allocates nothing for it, opens no profiler annotation and never waits on
the device. :func:`start` turns it on for the process; :func:`stop` turns it
off and returns a :class:`Trace` of what was recorded in between:

- spans, each with its name, host start and end (``time.perf_counter_ns``),
  the span open around it (``parent``), the node it ran on, the requests it
  served, the cluster clock at its start where the caller had a
  ``Network``, its attributes, and the backend compiles that ran while it
  was the innermost open span;
- counters: named integers, incremented where the work happens. The MoE
  layers' counters (:data:`MOE_COUNTERS`, through :func:`count_moe`) come
  from a small int array each decode step and prefill chunk returns beside
  its logits, read while the tracer is on at the next serving step's sync
  (or when the server falls idle or crashes): (token, expert) pairs
  routed, pairs that reached an expert held on this device, and held
  experts reached by at least one token, each summed over the MoE layers
  (the last is what a kernel that reads only the experts a step hits
  would read). ``host_paced_steps`` (:data:`HOST_PACED_STEPS`) counts the
  paged decode steps whose input tokens were ready on the device when the
  step came to read them: the host, not the chip, set their pace.

While on, every span also opens a ``jax.profiler.TraceAnnotation`` named
``discedge.<span name>`` (followed by the node id, where there is one). A
profile taken meanwhile holds the span's host interval on the timeline of
the device's ops, so a device idle gap can be put down to the program span
open during it.

A request is named by its session's context key where it has one (a
session has one turn in flight at a time, so the key names the turn), else
by the scheduler's request id. At most :data:`MAX_SPANS` spans are kept
between a start and a stop; later ones are counted in ``Trace.dropped``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

PREFIX = "discedge."
MAX_SPANS = 200_000
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

RequestIds = Union[None, int, str, Sequence[Union[int, str]]]


@dataclass
class Span:
    id: int
    name: str
    parent: Optional[int]
    node: Optional[str]
    requests: tuple
    clock_ms: Optional[float]    # cluster clock at the start, if known
    t0_ns: int = 0
    t1_ns: int = 0
    attrs: Dict[str, object] = field(default_factory=dict)
    compiles: int = 0

    @property
    def ms(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e6


@dataclass
class Trace:
    """What one start/stop recorded."""

    spans: List[Span]
    counters: Dict[str, int]
    dropped: int = 0

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def children(self) -> Dict[int, List[Span]]:
        """Span id -> the spans it directly caused."""
        out: Dict[int, List[Span]] = {}
        for s in self.spans:
            if s.parent is not None:
                out.setdefault(s.parent, []).append(s)
        return out


class _Null:
    """The span handed out while the tracer is off: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, key: str, value) -> None:
        pass

    def serve(self, requests) -> None:
        pass


_NULL = _Null()


class _Open:
    """A recorded span while it is open."""

    __slots__ = ("span", "_ann")

    def __init__(self, span: Span) -> None:
        self.span = span
        self._ann = None

    def __enter__(self):
        import jax

        s = self.span
        label = PREFIX + s.name if s.node is None else f"{PREFIX}{s.name} {s.node}"
        self._ann = jax.profiler.TraceAnnotation(label)
        self._ann.__enter__()
        _T.stack.append(s)
        s.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        s = self.span
        if not s.t1_ns:  # a stop() while open has closed it already
            s.t1_ns = time.perf_counter_ns()
        if _T.stack and _T.stack[-1] is s:
            _T.stack.pop()
        self._ann.__exit__(*exc)
        return False

    def set(self, key: str, value) -> None:
        self.span.attrs[key] = value

    def serve(self, requests) -> None:
        """Name the requests the span serves, once it knows them."""
        self.span.requests = tuple(requests)

    @property
    def ms(self) -> float:
        return self.span.ms


class _Stopwatch:
    """A measured block while the tracer is off (see :func:`timed`)."""

    __slots__ = ("t0_ns", "t1_ns")

    def __enter__(self):
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.t1_ns = time.perf_counter_ns()
        return False

    @property
    def ms(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e6


class _Tracer:
    def __init__(self) -> None:
        self.on = False
        self.listening = False
        self.spans: List[Span] = []
        self.stack: List[Span] = []
        self.counters: Dict[str, int] = {}
        self.dropped = 0


_T = _Tracer()


def enabled() -> bool:
    return _T.on


def _on_event(event: str, duration: float, **kw) -> None:
    if _T.on and event == COMPILE_EVENT:
        _T.counters["backend_compiles"] = _T.counters.get("backend_compiles", 0) + 1
        if _T.stack:
            _T.stack[-1].compiles += 1


def start() -> None:
    """Turn the tracer on, with nothing recorded yet."""
    if not _T.listening:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _T.listening = True
    _T.spans, _T.stack, _T.counters, _T.dropped = [], [], {}, 0
    _T.on = True


def stop() -> Trace:
    """Turn the tracer off and return what it recorded. A span still open
    ends here."""
    now = time.perf_counter_ns()
    for s in _T.stack:
        s.t1_ns = now
    out = Trace(spans=_T.spans, counters=_T.counters, dropped=_T.dropped)
    _T.on = False
    _T.spans, _T.stack, _T.counters, _T.dropped = [], [], {}, 0
    return out


def _open(name: str, node: Optional[str], requests: RequestIds, net) -> _Open:
    parent = _T.stack[-1] if _T.stack else None
    if node is None and parent is not None:
        node = parent.node
    if requests is None:
        ids: tuple = ()
    elif isinstance(requests, (int, str)):
        ids = (requests,)
    else:
        ids = tuple(requests)
    return _Open(Span(
        id=len(_T.spans), name=name,
        parent=parent.id if parent is not None else None, node=node,
        requests=ids, clock_ms=net.clock.now_ms if net is not None else None,
    ))


def span(name: str, node: Optional[str] = None, requests: RequestIds = None,
         net=None):
    """A context manager around one piece of work. ``node`` defaults to
    the enclosing span's; ``net`` (a ``Network``) stamps the cluster clock.
    The handle's ``set(key, value)`` records an attribute and its
    ``serve(ids)`` names the requests, where they are known only later."""
    if not _T.on:
        return _NULL
    if len(_T.spans) >= MAX_SPANS:
        _T.dropped += 1
        return _NULL
    op = _open(name, node, requests, net)
    _T.spans.append(op.span)
    return op


def timed(name: str, node: Optional[str] = None, requests: RequestIds = None,
          net=None):
    """:func:`span` for a block whose host time the caller needs whether
    or not the tracer is on: the handle's ``ms`` is that time, measured
    once."""
    op = span(name, node, requests, net)
    return op if op else _Stopwatch()


def count(name: str, n: int = 1) -> None:
    if _T.on:
        _T.counters[name] = _T.counters.get(name, 0) + n


MOE_COUNTERS = ("moe_routed_pairs", "moe_held_pairs", "moe_experts_hit")
HOST_PACED_STEPS = "host_paced_steps"
# the span around installing shipped or primed latent (MLA) pages
LATENT_PAGE_INSTALL = "serving.latent_page_install"


def count_moe(counts) -> None:
    """Add one step's or chunk's MoE counts (``models.moe.N_STATS``
    integers, in :data:`MOE_COUNTERS` order) to the counters."""
    for name, n in zip(MOE_COUNTERS, counts):
        count(name, int(n))
