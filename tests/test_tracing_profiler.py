"""Op-path spans in the JAX profiler capture (common/tracing.py).

With no capture running and the op unsampled a span site records and
allocates nothing; under a capture every span lands on the line of the
thread that ran it, inside the annotations around it, on the capture's
clock; and a small EC cluster's write and degraded read leave every
layer span of the op path, op-level ones carrying the client reqid; an
object's reader/writer state emits one osd:obj_wait span per acquisition.
"""

import asyncio
import glob
import os
import tracemalloc

import jax
import pytest

from ceph_tpu.common import tracing
from ceph_tpu.common.tracing import SpanCtx, Tracer
from ceph_tpu.msg import reset_local_namespace
from ceph_tpu.vstart import DevCluster

# every span the op path emits into a capture
CPU_SPANS = ("msgr:encode", "msgr:frame_out", "msgr:frame_in", "ec:prep",
             "ec:h2d", "ec:d2h", "ec:hinfo", "store:apply", "store:read")
WAIT_SPANS = ("osd:queue", "osd:fanout", "ec:coalesce_wait", "ec:launch")
HOP_SPANS = ("objecter:op_submit", "msgr:dispatch", "osd:do_op",
             "osd:ec:launch", "osd:sub_op:write:send", "osd:sub_op:write",
             "osd:sub_op:read:send", "osd:sub_op:read")
OP_LEVEL = ("objecter:op_submit", "osd:queue", "osd:do_op", "osd:fanout")


@pytest.fixture(autouse=True)
def _clean_local():
    reset_local_namespace()
    yield
    reset_local_namespace()


def _capture(tmp_path, body):
    """Run ``body()`` under a host-level-1 capture; the capture's host
    planes as [(line index, name, start_ns, end_ns, stats)]."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                events.append((li, ev.name, ev.start_ns, ev.end_ns,
                               dict(ev.stats)))
    return events


def _site(tracer: Tracer) -> None:
    """One op's worth of span sites, as the op path writes them."""
    with tracer.span("osd:do_op", None, root=False, reqid="c.1:7",
                     oid="obj"):
        with tracing.span("store:apply", oid="obj", shard=2):
            pass
    wait = tracing.span("ec:coalesce_wait")
    wait.end()


def test_idle_span_site_records_and_allocates_nothing(monkeypatch):
    assert not tracing.capturing()
    tracer = Tracer("osd.0")
    made = []
    real_init = tracing._Span.__init__

    def counting_init(self, *a):
        made.append(a[0])
        real_init(self, *a)

    monkeypatch.setattr(tracing._Span, "__init__", counting_init)
    monkeypatch.setattr(tracing.secrets, "token_hex",
                        lambda n: made.append("id") or "0" * (2 * n))
    for _ in range(100):
        _site(tracer)
    assert made == [] and len(tracer.spans) == 0
    # nothing is kept either: the traced heap does not grow with the
    # number of idle span sites run
    rounds = [None] * 20_000
    tracemalloc.start()
    try:
        before, _ = tracemalloc.get_traced_memory()
        for _ in rounds:
            _site(tracer)
        after, _ = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert after - before < 1024
    assert made == [] and len(tracer.spans) == 0
    # a sampled op still rings, capture or not
    with tracer.span("osd:do_op", SpanCtx("t1", "s1"), root=False) as ctx:
        assert ctx is not None and ctx.trace_id == "t1"
    assert [s["name"] for s in tracer.spans] == ["osd:do_op"]


def test_span_lands_inside_its_annotation_on_the_same_line(tmp_path):
    tracer = Tracer("osd.0")

    def body():
        with jax.profiler.TraceAnnotation("outer"):
            with tracer.span("osd:do_op", None, root=False,
                             reqid="c.1:7", oid="obj") as ctx:
                assert ctx is None          # nothing to propagate
                with tracing.span("store:apply", oid="obj", shard=2):
                    sum(range(1000))

    events = _capture(tmp_path, body)
    by = {name: (li, a, b, st) for li, name, a, b, st in events}
    assert {"outer", "osd:do_op", "store:apply"} <= set(by)
    (lo, ao, bo, _), (ld, ad, bd, sd), (ls, as_, bs, ss) = (
        by["outer"], by["osd:do_op"], by["store:apply"])
    assert lo == ld == ls
    assert ao <= ad <= as_ <= bs <= bd <= bo
    assert sd == {"reqid": "c.1:7", "oid": "obj"}
    assert ss == {"oid": "obj", "shard": 2}
    # an unsampled op leaves the ring empty while the capture sees it
    assert len(tracer.spans) == 0


def test_interleaved_coroutines_keep_their_own_intervals(tmp_path):
    async def op(i: int):
        sp = tracing.span("osd:queue", reqid=f"c.1:{i}")
        await asyncio.sleep(0.01 * (i + 1))
        sp.end()

    async def all_ops():
        with jax.profiler.TraceAnnotation("window"):
            await asyncio.gather(*(op(i) for i in range(3)))

    events = _capture(tmp_path, lambda: asyncio.run(all_ops()))
    window = next(e for e in events if e[1] == "window")
    spans = sorted((e for e in events if e[1] == "osd:queue"),
                   key=lambda e: e[4]["reqid"])
    assert len(spans) == 3
    for i, (line, _, a, b, stats) in enumerate(spans):
        assert line == window[0]
        assert window[2] <= a < b <= window[3]
        # each held across its own await: ~10, 20, 30 ms, overlapping
        assert b - a >= 0.01 * (i + 1) * 1e9 * 0.9
    assert spans[2][2] < spans[0][3]


def test_ec_write_and_degraded_read_emit_every_layer_span(tmp_path):
    async def run():
        cluster = DevCluster(n_mons=1, n_osds=3,
                             overrides={"osd_ec_resident": True})
        await cluster.start()
        try:
            rados = await cluster.client()
            r = await rados.mon_command(
                "osd erasure-code-profile set", name="sp21",
                profile={"plugin": "jax_rs", "k": "2", "m": "1",
                         "crush-failure-domain": "osd"})
            assert r["rc"] == 0, r
            await rados.pool_create("spans", pg_num=1,
                                    pool_type="erasure",
                                    erasure_code_profile="sp21")
            io = await rados.open_ioctx("spans")
            await io.write_full("warm", b"\x01" * 8192)
            pool = next(p.pool_id for p in
                        rados.monc.osdmap.pools.values()
                        if p.name == "spans")
            acting = rados.monc.osdmap.pg_to_up_acting(pool, 0)[2]
            victim = acting[1]          # a data shard, not the primary

            async def traffic():
                with jax.profiler.TraceAnnotation("window"):
                    await io.write_full("obj", b"\x5a" * 8192)
                    await cluster.kill_osd(victim)
                    r = await rados.mon_command("osd down", ids=[victim])
                    assert r["rc"] == 0, r
                    while rados.monc.osdmap.is_up(victim):
                        await asyncio.sleep(0.02)
                    assert await io.read("obj") == b"\x5a" * 8192

            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                await traffic()
            finally:
                jax.profiler.stop_trace()
            await rados.shutdown()
        finally:
            await cluster.stop()

    asyncio.run(run())
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    names: dict[str, list] = {}
    window_line = launch_lines = None
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                names.setdefault(ev.name, []).append(
                    (li, dict(ev.stats)))
                if ev.name == "window":
                    window_line = li
    missing = [n for n in CPU_SPANS + WAIT_SPANS + HOP_SPANS
               if n not in names]
    assert not missing, sorted(names)
    for n in OP_LEVEL:
        assert any(st.get("reqid") for _, st in names[n]), (n, names[n])
    # shard-level spans name the object and the shard
    assert any(st.get("oid") == "obj" and "shard" in st
               for _, st in names["store:apply"])
    # cpu spans run on the loop's line, the codec call on a worker's
    for n in CPU_SPANS:
        assert any(li == window_line for li, _ in names[n]), n
    launch_lines = {li for li, _ in names["ec:launch"]}
    assert window_line not in launch_lines


def test_contended_object_state_emits_one_wait_span(tmp_path):
    from ceph_tpu.common.perf import PerfCounters
    from ceph_tpu.osd.object_state import ObjectStates

    perf = PerfCounters("osd.0")
    table = ObjectStates(perf)

    async def run():
        gate = asyncio.Event()

        async def hold():
            async with table.lock("obj", "w", reqid="c.1:1"):
                await gate.wait()

        async def want():
            async with table.lock("obj", "r", reqid="c.1:2"):
                pass

        holder = asyncio.ensure_future(hold())
        await asyncio.sleep(0)
        waiter = asyncio.ensure_future(want())
        await asyncio.sleep(0.02)
        gate.set()
        await asyncio.gather(holder, waiter)

    events = _capture(tmp_path, lambda: asyncio.run(run()))
    spans = {st["reqid"]: (b - a, st) for _, n, a, b, st in events
             if n == "osd:obj_wait"}
    assert set(spans) == {"c.1:1", "c.1:2"}
    free, free_st = spans["c.1:1"]
    waited, waited_st = spans["c.1:2"]
    assert free_st == {"reqid": "c.1:1", "oid": "obj", "mode": "w",
                       "waited": 0}
    assert waited_st == {"reqid": "c.1:2", "oid": "obj", "mode": "r",
                         "waited": 1}
    # the contended one runs from its request to its grant, ~20 ms
    assert waited >= 0.02 * 1e9 * 0.9 > free
    assert perf.value("obj_rw_acquires") == 2
    assert perf.value("obj_rw_waits") == 1
