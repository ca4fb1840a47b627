"""Per-object reader/writer state of an EC primary.

The role of ``ObjectContext::RWState`` (reference src/osd/object_state.h)
as ``PrimaryLogPG::get_rw_locks`` takes it once per op vector: any number
of readers hold an object at once, a writer holds it alone, and waiters
are granted in arrival order — a reader that arrives behind a queued
writer waits for it, so a stream of reads on a hot object cannot starve
its writers, and two writes of one object commit in the order they
asked.

One table per ``ECBackend``, refcounted so it holds only objects that
are held or waited for.  A task that holds an object re-enters its own
grant instead of queueing behind itself: an op vector's ``write``,
``remove`` and ``read`` calls, or a scrub's repair, run under the one
state the outermost caller took.  Only the holding task re-enters; a
task it starts queues like any other.  An acquisition nobody contends
is granted without suspending.

Every real acquisition counts ``obj_rw_acquires`` (and ``obj_rw_waits``
when it had to wait) and, while a profiler capture runs, emits the wait
span ``osd:obj_wait`` from the request to the grant, tagged with
``mode`` and ``waited``: zero-length when uncontended.
"""

from __future__ import annotations

import asyncio
from collections import deque

from ceph_tpu.common import tracing
from ceph_tpu.common.perf import CounterType

READ, WRITE = "r", "w"


class _State:
    __slots__ = ("readers", "writer", "holders", "waiters", "refs")

    def __init__(self):
        self.readers = 0
        self.writer = False
        self.holders: dict[asyncio.Task, str] = {}     # task -> mode
        self.waiters: deque[tuple[str, asyncio.Future]] = deque()
        self.refs = 0           # holders + waiters

    def free_for(self, mode: str) -> bool:
        return not self.writer and (mode == READ or not self.readers)

    def take(self, mode: str) -> None:
        if mode == READ:
            self.readers += 1
        else:
            self.writer = True


class ObjectStates:
    """oid -> reader/writer state; ``lock(oid, mode)`` is the guard."""

    def __init__(self, perf):
        self._states: dict[str, _State] = {}
        self.perf = perf
        perf.add("obj_rw_acquires", CounterType.U64)
        perf.add("obj_rw_waits", CounterType.U64)

    def __len__(self) -> int:
        return len(self._states)

    def lock(self, oid: str, mode: str = WRITE, reqid=None) -> "_Guard":
        return _Guard(self, oid, mode, reqid)

    def held(self, oid: str) -> str | None:
        """The mode the running task holds ``oid`` in, if any."""
        st = self._states.get(oid)
        return st.holders.get(asyncio.current_task()) if st else None

    # -- grant and release ------------------------------------------------
    def _ref(self, oid: str) -> _State:
        st = self._states.get(oid)
        if st is None:
            st = self._states[oid] = _State()
        st.refs += 1
        return st

    def _unref(self, oid: str, st: _State) -> None:
        st.refs -= 1
        if st.refs <= 0:
            del self._states[oid]

    @staticmethod
    def _wake(st: _State) -> None:
        """Grant waiters in arrival order while the head is compatible."""
        while st.waiters:
            mode, fut = st.waiters[0]
            if fut.done():              # cancelled while waiting
                st.waiters.popleft()
                continue
            if not st.free_for(mode):
                return
            st.waiters.popleft()
            st.take(mode)
            fut.set_result(None)

    def _release(self, oid: str, st: _State, mode: str, task) -> None:
        st.holders.pop(task, None)
        if mode == READ:
            st.readers -= 1
        else:
            st.writer = False
        self._wake(st)
        self._unref(oid, st)


class _Guard:
    __slots__ = ("_table", "_oid", "_mode", "_reqid", "_st", "_task")

    def __init__(self, table: ObjectStates, oid: str, mode: str, reqid):
        self._table, self._oid, self._mode = table, oid, mode
        self._reqid = reqid
        self._st = None

    async def __aenter__(self):
        table, oid, mode = self._table, self._oid, self._mode
        held = table.held(oid)
        if held is not None:
            if held == READ and mode == WRITE:
                raise RuntimeError(
                    f"{oid}: write requested under this task's own read")
            return self             # re-entered: the outer grant covers it
        st = table._ref(oid)
        table.perf.inc("obj_rw_acquires")
        if not st.waiters and st.free_for(mode):
            st.take(mode)
            if tracing.capturing():
                tracing.span("osd:obj_wait", reqid=self._reqid, oid=oid,
                             tags={"mode": mode, "waited": 0}).end()
        else:
            table.perf.inc("obj_rw_waits")
            span = tracing.span("osd:obj_wait", reqid=self._reqid, oid=oid,
                                tags={"mode": mode, "waited": 1}) \
                if tracing.capturing() else tracing.NULL_SPAN
            fut = asyncio.get_running_loop().create_future()
            entry = (mode, fut)
            st.waiters.append(entry)
            try:
                await fut
            except BaseException:
                if fut.done() and not fut.cancelled():
                    # granted, then cancelled before it resumed
                    table._release(oid, st, mode, None)
                else:
                    try:
                        st.waiters.remove(entry)
                    except ValueError:
                        pass
                    table._wake(st)     # it may have blocked the head
                    table._unref(oid, st)
                raise
            finally:
                span.end()
        self._st, self._task = st, asyncio.current_task()
        st.holders[self._task] = mode
        return self

    async def __aexit__(self, *exc):
        st, self._st = self._st, None
        if st is not None:
            self._table._release(self._oid, st, self._mode, self._task)
        return False
