"""Spans and counters at the I/O stack's layer boundaries.

`span(name)` always opens a `jax.profiler.TraceAnnotation`, so every span
lands in the profiler's trace, on the device trace's clock, whenever a trace
is running. Inside `recording()` it is also kept in memory as a `Span`, with
the span that was open on the same thread as its parent; `count(name, n)`
adds to a counter. Outside `recording()` a span costs one check and the
annotation, and nothing is kept:

    with trace.recording() as rec:
        restore_checkpoint(store, "weights", template)
    rec.total("restore.read"), rec.counters["restore.store_requests"]

Spans go at layer boundaries and per leaf, step or wave, never inside a
per-block or per-byte loop: the engines count those in `PrefetchStats`.
While recording, JAX's compile events are counted too: `jax.compile_s` sums
the seconds spent tracing, lowering and compiling (a persistent-cache load
happens inside the compile event), and `jax.compiles` counts the programs
compiled or loaded from the cache. `tag(x, name)` counts where a staged
program goes: once each time the code holding it is lowered.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

# Compile events that JAX times; each program obtained fires the last once.
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Span(NamedTuple):
    name: str
    thread: int           # threading.get_ident() of the thread that ran it
    start_ns: int         # time.perf_counter_ns()
    end_ns: int
    parent: str | None    # the span open on the same thread at entry

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


@dataclass
class Recording:
    """What one `recording()` kept: spans in the order they ended, and
    counters. Filled while recording; final once the block exits."""

    spans: list[Span] = field(default_factory=list)
    counters: dict[str, float] = field(default_factory=dict)

    def total(self, name: str) -> float:
        """Summed seconds of every span called `name`."""
        return sum(s.seconds for s in self.spans if s.name == name)

    def self_s(self, name: str) -> float:
        """Summed self time of the spans called `name`: each one's seconds
        less those of its children on its thread."""
        own = [s for s in self.spans if s.name == name]
        inner = sum(c.seconds for c in self.spans if c.parent == name and any(
            c.thread == s.thread and s.start_ns <= c.start_ns and c.end_ns <= s.end_ns
            for s in own))
        return sum(s.seconds for s in own) - inner


class _Session:
    def __init__(self) -> None:
        self.rec = Recording()
        self.lock = threading.Lock()
        self.open = True
        self._local = threading.local()

    def stack(self) -> list[str]:
        """The calling thread's open spans, innermost last."""
        if not hasattr(self._local, "names"):
            self._local.names = []
        return self._local.names

    def add(self, name: str, n: float) -> None:
        with self.lock:
            if self.open:
                self.rec.counters[name] = self.rec.counters.get(name, 0) + n

    def on_compile(self, event: str, seconds: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.add("jax.compile_s", seconds)
            if event == _COMPILE_EVENTS[-1]:
                self.add("jax.compiles", 1)


_session: _Session | None = None
_session_lock = threading.Lock()


class _RecordedSpan:
    __slots__ = ("session", "name", "ann", "parent", "start_ns")

    def __init__(self, session: _Session, name: str) -> None:
        self.session = session
        self.name = name
        self.ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> None:
        self.ann.__enter__()
        stack = self.session.stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.start_ns = time.perf_counter_ns()

    def __exit__(self, *exc) -> None:
        end_ns = time.perf_counter_ns()
        s = self.session
        s.stack().pop()
        with s.lock:
            if s.open:
                s.rec.spans.append(Span(self.name, threading.get_ident(), self.start_ns,
                                        end_ns, self.parent))
        self.ann.__exit__(*exc)


def span(name: str):
    """A context manager timing `name`: a profiler annotation always, and a
    kept `Span` while the process is recording."""
    session = _session
    if session is None:
        return jax.profiler.TraceAnnotation(name)
    return _RecordedSpan(session, name)


def count(name: str, n: float = 1) -> None:
    """Add `n` to the counter `name` while the process is recording."""
    session = _session
    if session is not None:
        session.add(name, n)


def tag(x: jax.Array, name: str) -> jax.Array:
    """`x` unchanged; adds 1 to the counter `name` each time the code that
    computes it is lowered (or run op by op). Only the lowered platform's
    branch of a `jax.lax.platform_dependent` is lowered, so the counter
    tells which branch the compiled program holds. A recomputation under
    `jax.checkpoint` is lowered, and counts, again."""
    return _tag_p.bind(x, name=name)


def _count_and_return(x, *, name):
    count(name)
    return x


def _tag_lowering(ctx, x, *, name):
    count(name)
    return [x]


_tag_p = Primitive("trace_tag")
_tag_p.def_impl(_count_and_return)
_tag_p.def_abstract_eval(lambda x, *, name: x)
mlir.register_lowering(_tag_p, _tag_lowering)
ad.defjvp(_tag_p, lambda g, x, *, name: g)
batching.defvectorized(_tag_p)


@contextlib.contextmanager
def recording():
    """Keep every span and counter of the process until the block exits;
    yields the `Recording` they go into. One recording at a time."""
    global _session
    session = _Session()
    with _session_lock:
        if _session is not None:
            raise RuntimeError("the process is already recording")
        _session = session
    jax.monitoring.register_event_duration_secs_listener(session.on_compile)
    try:
        yield session.rec
    finally:
        jax.monitoring.unregister_event_duration_listener(session.on_compile)
        with _session_lock:
            _session = None
        with session.lock:
            session.open = False
