"""cephfs-journal-tool: offline MDS journal inspect/export/reset +
table show/reset (reference src/tools/cephfs/JournalTool.cc and
cephfs-table-tool)."""

import asyncio
import io
import json
import contextlib

import pytest

from ceph_tpu.client.fs import CephFS
from ceph_tpu.mds.daemon import _FRAME
from ceph_tpu.msg import reset_local_namespace
from ceph_tpu import cephfs_journal_tool as jt
from ceph_tpu.vstart import DevCluster


@pytest.fixture(autouse=True)
def _clean_local():
    reset_local_namespace()
    yield
    reset_local_namespace()


async def run_tool(conf, *argv):
    buf = io.StringIO()
    args = jt.build_parser().parse_args(["--conf", conf, *argv])
    with contextlib.redirect_stdout(buf):
        rc = await jt._run(args)
    return rc, buf.getvalue()


def test_journal_tool_lifecycle(tmp_path):
    async def run():
        cluster = DevCluster(n_mons=1, n_osds=3)
        await cluster.start()
        admin = await cluster.client()
        await admin.pool_create("cephfs_meta", pg_num=4, size=3,
                                min_size=2)
        await admin.pool_create("cephfs_data", pg_num=4, size=3,
                                min_size=2)
        mds = await cluster.start_mds(name="a", block_size=4096)
        conf = str(tmp_path / "c.json")
        cluster.write_conf(conf)
        try:
            rc = await cluster.client("client.w")
            fs = await CephFS.connect(rc)
            await fs.mount()
            await fs.mkdir("/d")
            await fs.write_file("/d/f", b"x")
            await fs.unmount()
            await rc.shutdown()
            # inspect: clean log with the ops we just generated
            code, out = await run_tool(conf, "journal", "inspect")
            rep = json.loads(out)
            assert code == 0 and rep["overall"] == "OK"
            assert rep["events"] > 0 and rep["ops"].get("mkdir") == 1
            # event get list filters by op
            code, out = await run_tool(conf, "event", "get", "list",
                                       "--op", "mkdir")
            evs = json.loads(out)
            assert len(evs) == 1 and evs[0]["name"] == "d"
            # export returns every decoded event
            code, out = await run_tool(conf, "journal", "export")
            assert len(json.loads(out)) == rep["events"]
            # table show: rank-0 watermark + subtree map exist
            code, out = await run_tool(conf, "table", "show")
            tab = json.loads(out)
            assert int(tab["inotable"].get("0", 0)) > 0 or \
                tab["inotable"] == {}    # may be pre-first-compact
            # damage the tail: inspect localises it, exit code 1
            meta = await admin.open_ioctx("cephfs_meta")
            await meta.append("mds_journal",
                              _FRAME.pack(9999) + b"short")
            code, out = await run_tool(conf, "journal", "inspect")
            rep = json.loads(out)
            assert code == 1 and rep["overall"] == "DAMAGED"
            assert "torn tail" in rep["damage"]
            # reset clears the damage; the MDS boots clean after
            code, out = await run_tool(conf, "journal", "reset")
            assert json.loads(out)["was_damaged"] is True
            code, out = await run_tool(conf, "journal", "inspect")
            assert json.loads(out)["overall"] == "OK"
            # table reset puts the allocator at the partition floor
            code, out = await run_tool(conf, "table", "reset",
                                       "--rank", "0")
            assert json.loads(out)["next_ino"] > 1
            await admin.shutdown()
        finally:
            await cluster.stop()
    asyncio.run(run())


def test_walk_frames_pure():
    """Frame walker damage classes without a cluster."""
    from ceph_tpu.msg.codec import encode
    ev = encode({"op": "mkdir", "ino": 5})
    clean = _FRAME.pack(len(ev)) + ev
    events, good, damage = jt.walk_frames(clean * 3)
    assert len(events) == 3 and not damage and good == len(clean) * 3
    # torn tail
    events, good, damage = jt.walk_frames(clean + clean[:7])
    assert len(events) == 1 and "torn tail" in damage
    # trailing garbage shorter than a header
    events, good, damage = jt.walk_frames(clean + b"\x01")
    assert len(events) == 1 and "trailing" in damage
    # undecodable payload
    bad = _FRAME.pack(4) + b"\xff\xff\xff\xff"
    events, good, damage = jt.walk_frames(clean + bad)
    assert len(events) == 1 and "undecodable" in damage
    # open-intent bookkeeping
    ints = jt.open_intents([
        {"op": "rename_export_intent", "token": "t1"},
        {"op": "rename_export_intent", "token": "t2"},
        {"op": "rename_export_finish", "token": "t1"},
    ])
    assert set(ints) == {"t2"}
