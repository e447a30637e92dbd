"""The program's spans and counters.

``span(name, **attrs)`` always opens a ``jax.profiler.TraceAnnotation``,
so the span lands on the host plane of the profiler's trace, on the same
clock as the device's ops.  Only while a profiler session is active does
it also keep a record in memory: name, start and end
(``time.perf_counter_ns``), the enclosing span, and a request id that every
span under one root shares.  ``count(name, n)`` adds to a counter under the
same gate.  With no session active a span costs one ``is_enabled`` check
and records nothing, so untraced runs keep nothing.

``spans()``, ``counters()`` and ``summary()`` read what was recorded;
``reset()`` clears it; ``enabled()`` says whether the gate is open.  ``python -m repro.launch.plan --profile DIR``
prints the summary of one decision.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax

try:
    from jax._src.lib import _profiler as _xprof
    _is_enabled = _xprof.TraceMe.is_enabled
except (ImportError, AttributeError):      # no such gate: never record
    def _is_enabled() -> bool:
        return False

#: records kept; later spans are dropped and counted as ``obs.dropped``
MAX_SPANS = 1_000_000


@dataclasses.dataclass(frozen=True)
class Span:
    id: int
    name: str
    parent: Optional[int]         # id of the enclosing span; None: a root
    request: int                  # id of the root this span runs under
    start_ns: int
    end_ns: int
    attrs: dict

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


_ids = itertools.count(1)
_open = threading.local()         # .stack: [(id, request)] of open spans
_lock = threading.Lock()
_spans: List[Span] = []
_counters: Dict[str, int] = {}


def _add(name: str, n) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the enclosed block as span ``name`` (also a decorator)."""
    with jax.profiler.TraceAnnotation(name, **attrs):
        if not _is_enabled():
            yield
            return
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        sid = next(_ids)
        parent, request = stack[-1] if stack else (None, sid)
        stack.append((sid, request))
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            stack.pop()
            rec = Span(sid, name, parent, request, t0, t1, attrs)
            with _lock:
                full = len(_spans) >= MAX_SPANS
                if not full:
                    _spans.append(rec)
            if full:
                _add("obs.dropped", 1)


def enabled() -> bool:
    """Whether a profiler session is active: spans are recorded and
    counters add."""
    return _is_enabled()


def count(name: str, n) -> None:
    """Add ``n`` to counter ``name`` while a profiler session is active."""
    if _is_enabled():
        _add(name, n)


def spans() -> List[Span]:
    with _lock:
        return list(_spans)


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def reset() -> None:
    with _lock:
        _spans.clear()
        _counters.clear()


def self_ns(records: Iterable[Span]) -> Dict[int, int]:
    """``{span id: self time}``: each span's duration less the part of it
    that its child spans cover."""
    records = list(records)
    kids: Dict[int, list] = {}
    for s in records:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append((s.start_ns, s.end_ns))
    out = {}
    for s in records:
        cover, reach = 0, s.start_ns
        for a, b in sorted(kids.get(s.id, ())):
            a, b = max(a, reach), min(b, s.end_ns)
            if b > a:
                cover += b - a
                reach = b
        out[s.id] = s.duration_ns - cover
    return out


def summary(records: Optional[Iterable[Span]] = None
            ) -> Dict[str, Tuple[int, int, int]]:
    """``{name: (count, total ns, self ns)}`` over ``records`` (default:
    everything recorded)."""
    records = spans() if records is None else list(records)
    own = self_ns(records)
    out: Dict[str, list] = {}
    for s in records:
        row = out.setdefault(s.name, [0, 0, 0])
        row[0] += 1
        row[1] += s.duration_ns
        row[2] += own[s.id]
    return {k: tuple(v) for k, v in out.items()}
