"""Op-path tracing: one span API, two sinks.

**Sampled ring** (the role of reference src/common/zipkin_trace.h:24
ZTracer wrappers + the OpRequest trace hooks, src/osd/OpRequest.h): a
sampled client op carries a trace context on the wire; every hop
(objecter submit, OSD op execution, sub-op fan-out, replica apply)
records a timed span linked by (trace_id, parent span id).  Spans land
in a bounded per-process ring inspectable via the admin socket /
``dump_traces`` message, keyed so a cross-daemon trace tree can be
reassembled.  The root decides (``trace_probability`` config);
everything downstream of a sampled op traces unconditionally, so a
trace is always complete.

**Profiler capture**: while a JAX profiler capture runs in this process
(``jax.profiler.start_trace`` with ``host_tracer_level >= 1``, or an
operator's capture over the profiler server), every span — sampled or
not — is also emitted into it as a ``TraceAnnotation`` on the thread
that runs the code, with its identifiers (``reqid``, ``oid``,
``shard``) as metadata.  The spans then share the device trace's clock:
what the host did while the chip idled is read off one timeline.

Layer spans name where the op path spends the host's time.  A *cpu*
span encloses a synchronous section (no ``await`` inside), so on the
event loop's thread it is what the loop was doing: ``msgr:encode``,
``msgr:frame_out``, ``msgr:frame_in``, ``ec:prep``, ``ec:h2d``,
``ec:d2h``, ``ec:hinfo``, ``store:apply``, ``store:read``.  A *wait*
span encloses awaits:
``osd:queue``, ``osd:obj_wait``, ``osd:fanout``, ``ec:coalesce_wait``,
``ec:repair`` (a degraded read's rebuild, from its plan to the rebuilt
chunk, its byte counts set as tags at its end through ``set_metadata``),
and ``ec:launch`` on the worker thread that runs the codec call.

With no capture running and the op not sampled, a span site costs one
flag check and returns the shared no-op ``NULL_SPAN``: no dict, no id,
no string is built.  Span sites therefore pass identifiers as plain
keyword arguments (never a built dict or f-string) and let the span
format them only when it records.
"""

from __future__ import annotations

import contextvars
import secrets
import sys
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass

_RING = 4096


@dataclass(frozen=True)
class SpanCtx:
    trace_id: str
    span_id: str

    def to_wire(self) -> dict:
        return {"t": self.trace_id, "s": self.span_id}

    @staticmethod
    def from_wire(d) -> "SpanCtx | None":
        if not isinstance(d, dict) or "t" not in d:
            return None
        return SpanCtx(str(d["t"]), str(d.get("s", "")))


# The task-local active span: set where an op's span is opened (RGW
# request handler, OSD do_op, EC per-op submit) and read at the next
# layer down (objecter, EC coalescer, messenger) so causality crosses
# module boundaries without threading a ctx argument through every
# signature.  A contextvar — each asyncio task sees its own value.
_ACTIVE: contextvars.ContextVar[SpanCtx | None] = contextvars.ContextVar(
    "tracing_active_span", default=None
)


def current_span() -> SpanCtx | None:
    """The ambient SpanCtx of the running task, if any."""
    return _ACTIVE.get()


@contextmanager
def use_span(ctx: SpanCtx | None):
    """Make ``ctx`` the ambient span for the enclosed block."""
    tok = _ACTIVE.set(ctx)
    try:
        yield ctx
    finally:
        _ACTIVE.reset(tok)


# -- the profiler sink ----------------------------------------------------

_Note = None            # jaxlib's TraceMe with end(), once JAX is loaded


def _probe() -> bool:
    """``capturing`` until JAX is loaded: without JAX in the process no
    capture can run, and nothing is imported.  Once it is, the module
    name ``capturing`` is rebound to jaxlib's own flag check, so a span
    site costs that one call — callers look it up as
    ``tracing.capturing``, never by a from-import."""
    global capturing, _Note
    lib = sys.modules.get("jax._src.lib")
    profiler = getattr(lib, "_profiler", None)
    if profiler is None:
        return False

    class Note(profiler.TraceMe):
        """An annotation in the running capture: it starts when made and
        stops on leaving its ``with`` block or at ``end()``, once.  Its
        ``with`` yields None: no context to propagate."""

        __slots__ = ()

        def __enter__(self):
            super().__enter__()

        def end(self) -> None:
            self.__exit__(None, None, None)

    _Note = Note
    capturing = profiler.TraceMe.is_enabled
    return capturing()


capturing = _probe


def _meta(reqid, oid, shard, tags) -> dict:
    """A span's tags: ``tags`` and its identifiers that are set."""
    meta = dict(tags) if tags else {}
    if reqid is not None:
        meta["reqid"] = reqid
    if oid is not None:
        meta["oid"] = oid
    if shard is not None:
        meta["shard"] = shard
    return meta


def _note(name, reqid, oid, shard, tags):
    """The capture's annotation of one span, its tags as metadata."""
    if reqid is None and oid is None and shard is None and not tags:
        return _Note(name)
    return _Note(name, **_meta(reqid, oid, shard, tags))


class _NullSpan:
    """The span of an op that is neither sampled nor captured."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False

    def end(self) -> None:
        pass

    def set_metadata(self, **tags) -> None:
        pass


NULL_SPAN = _NullSpan()


class _Span:
    """A span of a sampled op: its ring record, and its annotation while
    a capture runs.  Starts when made; ``end()`` (or leaving the
    ``with`` block) stops it, once — so a wait span may start at one
    site and end at another on the same thread."""

    __slots__ = ("ctx", "_note", "_tracer", "_rec", "_t0")

    def __init__(self, name, tracer, parent, reqid, oid, shard, tags):
        meta = _meta(reqid, oid, shard, tags)
        ctx = SpanCtx(
            parent.trace_id if parent else secrets.token_hex(8),
            secrets.token_hex(4),
        )
        self.ctx, self._tracer = ctx, tracer
        # wall-clock start for cross-daemon ordering, monotonic clock
        # for the duration (an NTP step must not yield negative spans)
        self._rec = {
            "trace_id": ctx.trace_id,
            "span_id": ctx.span_id,
            "parent": parent.span_id if parent else "",
            "name": name,
            "entity": tracer.entity,
            "start": time.time(),
            **({"tags": meta} if meta else {}),
        }
        self._t0 = time.perf_counter()
        self._note = _Note(name, **meta) if capturing() else None

    def __enter__(self):
        return self.ctx

    def __exit__(self, *exc):
        self.end()
        return False

    def end(self) -> None:
        note, self._note = self._note, None
        if note is not None:
            note.__exit__(None, None, None)
        rec, self._rec = self._rec, None
        if rec is not None:
            rec["duration_ms"] = round(
                (time.perf_counter() - self._t0) * 1e3, 3)
            self._tracer._append(rec)


def span(name: str, *, reqid=None, oid=None, shard=None, tags=None):
    """A layer span into the running capture (no ring: layer spans are
    for the profiler's timeline); ``NULL_SPAN`` when none runs.  Build
    ``tags`` only where ``capturing()`` said a capture runs."""
    if not capturing():
        return NULL_SPAN
    return _note(name, reqid, oid, shard, tags)


class Tracer:
    """Per-process span collector (one per daemon entity)."""

    def __init__(self, entity: str):
        self.entity = entity
        self.spans: deque[dict] = deque(maxlen=_RING)
        #: spans pushed out of the bounded ring before collection —
        #: each eviction is a potential orphan in a later
        #: ``assemble_tree``, so span loss must be visible *before*
        #: a trace is pulled (perf counter / prom gauge)
        self.ring_evictions = 0

    def _append(self, span: dict) -> None:
        if len(self.spans) == self.spans.maxlen:
            self.ring_evictions += 1
        self.spans.append(span)

    def span(self, name: str, parent: SpanCtx | None = None, *,
             root: bool = True, reqid=None, oid=None, shard=None,
             tags: dict | None = None):
        """Time the enclosed block (sync or async: it only stamps
        clocks) as span ``name``; ``with`` yields the child SpanCtx to
        propagate, or None when the ring does not record it.

        The ring records it under ``parent``; with no parent it opens a
        new trace, unless ``root=False``: an op-path site, which spans
        every op, passes the op's sampled context or None.  The running
        capture, if any, gets the span either way.
        ``reqid``/``oid``/``shard`` and ``tags`` ride both as the span's
        tags; build ``tags`` only where the ring records."""
        if parent is None and not root:
            if not capturing():
                return NULL_SPAN
            return _note(name, reqid, oid, shard, tags)
        return _Span(name, self, parent, reqid, oid, shard, tags)

    def record(self, name: str, parent: SpanCtx, start: float,
               duration_ms: float, **tags) -> SpanCtx:
        """Append a pre-measured span to the ring (no context manager).
        For work shared across ops — a coalesced device launch serves
        many traces at once, so the one measured interval is recorded
        once per interested parent.  A capture cannot take an interval
        after the fact: the site that measured it runs it inside a live
        ``span`` for the profiler."""
        ctx = SpanCtx(parent.trace_id, secrets.token_hex(4))
        self._append({
            "trace_id": ctx.trace_id,
            "span_id": ctx.span_id,
            "parent": parent.span_id,
            "name": name,
            "entity": self.entity,
            "start": start,
            "duration_ms": round(duration_ms, 3),
            **({"tags": tags} if tags else {}),
        })
        return ctx

    def dump(self, trace_id: str | None = None) -> list[dict]:
        return [s for s in self.spans
                if trace_id is None or s["trace_id"] == trace_id]

    def orphan_count(self) -> int:
        """Spans currently in the ring whose parent has already fallen
        out of it — what ``assemble_tree`` would tag ``orphan`` if a
        collection ran now.  O(ring) walk; called at perf-dump time,
        not on the span hot path."""
        ids = {s["span_id"] for s in self.spans}
        return sum(1 for s in self.spans
                   if s.get("parent") and s["parent"] not in ids)


def assemble_tree(spans: list[dict]) -> list[dict]:
    """Merge spans (possibly from several daemons) into parent-linked
    trees sorted by start time — the trace-view the reference gets
    from its zipkin collector."""
    by_id = {s["span_id"]: dict(s) for s in spans}
    roots: list[dict] = []
    for s in sorted(by_id.values(), key=lambda s: s["start"]):
        pid = s.get("parent", "")
        parent = by_id.get(pid)
        if parent is not None:
            parent.setdefault("children", []).append(s)
        else:
            # a span naming a parent that isn't in the set (fell out
            # of the ring, or a daemon wasn't collected) is promoted
            # to a root but marked, so partial traces are
            # distinguishable from genuinely root spans
            if pid:
                s["orphan"] = True
            roots.append(s)
    return roots
