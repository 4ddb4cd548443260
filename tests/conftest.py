"""Shared test setup.

Provides the opt-in ``traced_locks`` fixture: it swaps
``threading.Lock/RLock/Condition`` for recording wrappers so a test's
*actual* lock acquisition order is captured, then (teardown) asserts
every observed nesting is consistent with the static lock-order graph
that ``repro.analysis`` extracts from the source — i.e. that adding the
observed edges to the static graph introduces no cycle. Set
``REPRO_LOCK_ORDER=1`` to apply it automatically to the concurrency
suites (test_cache_tiers, test_peer).
"""

from __future__ import annotations

import os
import sys
import threading

import pytest


# Imported at collection time, before any fixture patches threading's
# constructor names — repro.sched captures the real ones at import, and
# both this file's traced locks and the interleaving explorer's
# cooperative locks go through the same patch mechanism.
from repro.sched import patch_threading_ctors


# ---------------------------------------------------------------------------
# Instrumented locks: record runtime acquisition order, check it against
# the static lock graph.
# ---------------------------------------------------------------------------

_REAL_LOCK = threading.Lock
_REAL_RLOCK = threading.RLock
_REAL_CONDITION = threading.Condition


class LockOrderRecorder:
    """Collects (outer, inner) lock-name pairs as threads nest locks.

    Lock names resolve lazily at first acquire by scanning caller frames
    for a ``self`` whose ``__dict__`` holds the wrapper — yielding the
    same ``ClassName._attr`` naming the static analyzer uses. Locks that
    never resolve (locals, module globals) record no edges, mirroring the
    static graph's scope.
    """

    def __init__(self) -> None:
        self.edges: dict[tuple[str, str], str] = {}
        self._held = threading.local()
        self._mu = _REAL_LOCK()

    def _stack(self) -> list:
        st = getattr(self._held, "stack", None)
        if st is None:
            st = self._held.stack = []
        return st

    def on_acquire(self, wrapper) -> None:
        name = wrapper._name or wrapper._resolve_name()
        stack = self._stack()
        if name is not None:
            for held in stack:
                hname = held._name
                if hname is None or hname == name:
                    continue
                with self._mu:
                    self.edges.setdefault(
                        (hname, name), threading.current_thread().name
                    )
        stack.append(wrapper)

    def on_release(self, wrapper) -> None:
        stack = self._stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is wrapper:
                del stack[i]
                break


class _TracedLock:
    """Wrapper over a real Lock/RLock/Condition that reports to a
    recorder. Everything not intercepted delegates to the inner object."""

    def __init__(self, recorder: LockOrderRecorder, inner) -> None:
        self._recorder = recorder
        self._inner = inner
        self._name: str | None = None

    def _resolve_name(self) -> str | None:
        f = sys._getframe(2)
        for _ in range(12):
            if f is None:
                return None
            owner = f.f_locals.get("self")
            if owner is not None and owner is not self:
                try:
                    d = object.__getattribute__(owner, "__dict__")
                except AttributeError:
                    d = {}
                for attr, val in list(d.items()):
                    if val is self:
                        self._name = f"{type(owner).__name__}.{attr}"
                        return self._name
            f = f.f_back
        return None

    def acquire(self, *args, **kwargs):
        got = self._inner.acquire(*args, **kwargs)
        if got:
            self._recorder.on_acquire(self)
        return got

    def release(self) -> None:
        self._recorder.on_release(self)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _TracedCondition(_TracedLock):
    def wait(self, timeout=None):
        # wait() releases and reacquires the underlying lock; mirror that
        # in the held stack so edges recorded across the wakeup are real.
        self._recorder.on_release(self)
        try:
            return self._inner.wait(timeout)
        finally:
            self._recorder.on_acquire(self)

    def wait_for(self, predicate, timeout=None):
        self._recorder.on_release(self)
        try:
            return self._inner.wait_for(predicate, timeout)
        finally:
            self._recorder.on_acquire(self)

    def notify(self, n: int = 1) -> None:
        self._inner.notify(n)

    def notify_all(self) -> None:
        self._inner.notify_all()


_restore_lock_ctors = None


def _patch_lock_ctors(recorder: LockOrderRecorder):
    global _restore_lock_ctors

    def make_lock():
        return _TracedLock(recorder, _REAL_LOCK())

    def make_rlock():
        return _TracedLock(recorder, _REAL_RLOCK())

    def make_condition(lock=None):
        if isinstance(lock, _TracedLock):
            lock = lock._inner
        return _TracedCondition(recorder, _REAL_CONDITION(lock))

    _restore_lock_ctors = patch_threading_ctors(
        lock=make_lock, rlock=make_rlock, condition=make_condition)


def _unpatch_lock_ctors() -> None:
    global _restore_lock_ctors
    if _restore_lock_ctors is not None:
        _restore_lock_ctors()
        _restore_lock_ctors = None


@pytest.fixture(scope="session")
def static_lock_graph():
    """The analyzer's lock-order graph over src/, built once per run."""
    from repro.analysis import build_lock_graph, load_project

    root = os.path.join(os.path.dirname(__file__), os.pardir)
    project, _ = load_project([os.path.join(root, "src")])
    return build_lock_graph(project)


def assert_order_consistent(recorder: LockOrderRecorder, graph) -> None:
    """Every observed (outer → inner) edge must be compatible with the
    static graph: the static graph must not already order inner BEFORE
    outer (a path inner → outer), or the union would be cyclic."""
    violations = []
    for (outer, inner), thread in sorted(recorder.edges.items()):
        a, b = graph.normalize(outer), graph.normalize(inner)
        if a == b:
            continue
        if graph.has_path(b, a):
            violations.append(
                f"runtime acquired {outer} then {inner} (thread {thread}), "
                f"but the static graph orders {b} before {a}"
            )
    assert not violations, (
        "runtime lock order contradicts static lock graph:\n  "
        + "\n  ".join(violations)
    )


@pytest.fixture
def traced_locks(static_lock_graph):
    """Opt-in: record this test's real lock acquisition order and check
    it against the static graph on teardown."""
    recorder = LockOrderRecorder()
    _patch_lock_ctors(recorder)
    try:
        yield recorder
    finally:
        _unpatch_lock_ctors()
    assert_order_consistent(recorder, static_lock_graph)


@pytest.fixture(autouse=True)
def _lock_order_autocheck(request):
    """With REPRO_LOCK_ORDER=1, apply traced_locks to the concurrency
    suites without editing each test."""
    if os.environ.get("REPRO_LOCK_ORDER") and request.module.__name__ in (
        "test_cache_tiers",
        "test_peer",
    ):
        request.getfixturevalue("traced_locks")
    yield
