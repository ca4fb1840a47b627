"""Offline disaster-recovery tool suite (reference ceph-monstore-tool,
osdmaptool, monmaptool + ceph-objectstore-tool update-mon-db).

Covers: monstore dump/get round-trips, rebuild-transaction layout,
monmaptool edits, upmap proposal validity, --test-map-pgs bit-identity
against a live cluster's pg_to_up_acting, and the headline DR e2e:
write replicated + EC objects, kill and WIPE every monitor, rebuild
the mon store from the surviving OSD stores, author a brand-new quorum
with monmaptool, restart, and read every object back bit-identical.
"""

import argparse
import asyncio
import json
import shutil

import pytest

from ceph_tpu import objectstore_tool
from ceph_tpu.mon.store import MonitorDBStore, StoreTransaction
from ceph_tpu.msg import reset_local_namespace
from ceph_tpu.msg.codec import decode, encode
from ceph_tpu.osd.osd_map import NO_OSD, OSDMap
from ceph_tpu.tools import monmaptool, monstore_tool, osdmaptool
from ceph_tpu.vstart import DevCluster


@pytest.fixture(autouse=True)
def _clean_local():
    reset_local_namespace()
    yield
    reset_local_namespace()


def _tool(mod, *argv):
    """Drive a tool's argv surface inside the caller's loop (the
    rbd_tool pattern: main() owns its own asyncio.run, which a
    loop-bound local:// cluster cannot share)."""
    return mod._run(mod.build_parser().parse_args(list(argv)))


# -- constants contract -----------------------------------------------------
def test_objectstore_tool_constants_match_daemon():
    """The harvest layer addresses the SAME meta collection/objects the
    daemon writes — drift here silently empties every rebuild."""
    from ceph_tpu import objectstore_tool as ot
    from ceph_tpu.osd.daemon import OSDDaemon

    assert ot.META_CID == OSDDaemon._SUPER_CID
    assert ot.SUPERBLOCK_OID == OSDDaemon._SUPER_OID
    assert ot.MAPS_OID == OSDDaemon._MAPS_OID


# -- monstore_tool: dump / get / install ------------------------------------
def test_monstore_dump_get_round_trip(tmp_path, capsys):
    path = str(tmp_path / "mon.x")
    tx = (StoreTransaction()
          .put("osdmap", "last_committed", 7)
          .put("osdmap", "full_7", encode({"epoch": 7}))
          .put("auth", "entity/client.admin",
               json.dumps({"key": "k"}).encode()))
    MonitorDBStore.install(path, tx)

    async def run():
        assert await _tool(monstore_tool, "dump",
                           "--store-path", path) == 0
        dump = json.loads(capsys.readouterr().out)
        assert dump["osdmap"]["last_committed"] == 1   # size of b"7"
        assert set(dump["osdmap"]) == {"last_committed", "full_7"}

        assert await _tool(monstore_tool, "get", "--store-path", path,
                           "osdmap", "last_committed") == 0
        got = json.loads(capsys.readouterr().out)
        assert got["value"] == 7
        assert await _tool(monstore_tool, "get", "--store-path", path,
                           "osdmap", "full_7") == 0
        assert json.loads(capsys.readouterr().out)["value"] == \
            {"epoch": 7}
        # auth entity decodes as json
        assert await _tool(monstore_tool, "get", "--store-path", path,
                           "auth", "entity/client.admin") == 0
        assert json.loads(capsys.readouterr().out)["value"]["key"] \
            == "k"
        # missing key / missing store are rc 1, not tracebacks
        assert await _tool(monstore_tool, "get", "--store-path", path,
                           "osdmap", "nope") == 1
        assert await _tool(monstore_tool, "dump", "--store-path",
                           str(tmp_path / "missing")) == 1

    asyncio.run(run())


def test_monstore_install_preserves_old_store(tmp_path):
    """The two-phase swap keeps the previous store as a forensic
    corpse and the new store replays cleanly."""
    path = str(tmp_path / "mon.y")
    MonitorDBStore.install(
        path, StoreTransaction().put("osdmap", "last_committed", 1))
    MonitorDBStore.install(
        path, StoreTransaction().put("osdmap", "last_committed", 2))
    st = MonitorDBStore.open_readonly(path)
    assert st.get_int("osdmap", "last_committed") == 2
    assert (tmp_path / "mon.y" / "store.wal.old").exists()


def test_build_rebuild_tx_layout(tmp_path):
    epochs = {3: {"epoch": 3}, 5: {"epoch": 5}, 4: {"epoch": 4}}
    secrets = {9: "s9", 11: "s11"}
    tx = monstore_tool.build_rebuild_tx(epochs, secrets,
                                        admin_key="adm", keep=2)
    path = str(tmp_path / "mon.z")
    MonitorDBStore.install(path, tx)
    st = MonitorDBStore.open_readonly(path)
    assert st.get_int("osdmap", "last_committed") == 5
    # keep=2 retains only the newest epochs
    assert sorted(st.keys("osdmap")) == ["full_4", "full_5",
                                         "last_committed"]
    assert decode(st.get("osdmap", "full_5")) == {"epoch": 5}
    ent = json.loads(st.get("auth", "entity/client.admin"))
    assert ent["key"] == "adm" and "mon" in ent["caps"]
    assert json.loads(st.get("auth", "secret/11"))["secret"] == "s11"
    # paxos: one synthesized version carrying the whole service state
    assert st.get_int("paxos", "first_committed") == 1
    assert st.get_int("paxos", "last_committed") == 1
    replayed = StoreTransaction.decode(st.get("paxos", "1"))
    assert ("put", "osdmap", "last_committed", b"5") in replayed.ops
    with pytest.raises(ValueError):
        monstore_tool.build_rebuild_tx({}, {})


# -- monmaptool -------------------------------------------------------------
def test_monmaptool_round_trip(tmp_path, capsys):
    conf = str(tmp_path / "cluster.json")

    async def run():
        assert await _tool(monmaptool, conf, "--create",
                           "--add", "a", "local://mon.a",
                           "--add", "b", "local://mon.b") == 0
        # cluster-conf shape: daemons read doc["monmap"]
        doc = json.loads((tmp_path / "cluster.json").read_text())
        assert doc["monmap"] == {"a": "local://mon.a",
                                 "b": "local://mon.b"}
        assert "overrides" in doc
        # add at a conflicting address is refused
        capsys.readouterr()
        assert await _tool(monmaptool, conf, "--add", "a",
                           "local://elsewhere") == 1
        assert await _tool(monmaptool, conf, "--rm", "b") == 0
        assert await _tool(monmaptool, conf, "--rm", "b") == 1
        capsys.readouterr()
        assert await _tool(monmaptool, conf, "--print") == 0
        out = json.loads(capsys.readouterr().out)
        assert out["mons"] == {"a": "local://mon.a"}
        assert out["num_mons"] == 1
        # --create without --clobber refuses to stomp a live conf
        assert await _tool(monmaptool, conf, "--create") == 1
        assert await _tool(monmaptool, conf, "--create",
                           "--clobber", "--add", "m",
                           "local://mon.m") == 0
        doc = json.loads((tmp_path / "cluster.json").read_text())
        assert doc["monmap"] == {"m": "local://mon.m"}

    asyncio.run(run())


# -- live-cluster coverage ---------------------------------------------------
async def _wait_active(cluster, pool_id, timeout=20.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while True:
        states = []
        for osd in cluster.osds.values():
            for pgid, pg in osd.pgs.items():
                if pgid.pool == pool_id and pg.is_primary:
                    states.append(pg.state)
        if states and all(s == "active" for s in states):
            return
        if asyncio.get_running_loop().time() > deadline:
            raise TimeoutError(f"pgs not active: {states}")
        await asyncio.sleep(0.05)


async def _wait_osd_epochs(cluster, epoch, timeout=10.0):
    """Every OSD has received (and therefore persisted to its map
    history) the given epoch."""
    deadline = asyncio.get_running_loop().time() + timeout
    while True:
        if all(o.osdmap is not None and o.osdmap.epoch >= epoch
               for o in cluster.osds.values()):
            return
        if asyncio.get_running_loop().time() > deadline:
            raise TimeoutError("OSDs never caught up to mon epoch")
        await asyncio.sleep(0.05)


def test_dr_rebuild_after_total_mon_loss(tmp_path, capsys):
    """The headline DR scenario: replicated + EC data, all monitors
    killed AND wiped, mon store rebuilt offline from the surviving OSD
    stores, a new quorum authored with monmaptool, cluster restarted —
    every object reads back bit-identical.  Along the way the offline
    osdmaptool simulation is checked bit-identical against the live
    cluster's pg_to_up_acting at the same epoch, and upmap proposals
    are validated against the rebuilt map."""
    store_dir = tmp_path / "run"
    store_dir.mkdir()

    async def run():
        cluster = DevCluster(n_mons=1, n_osds=4,
                             store_dir=str(store_dir))
        await cluster.start()
        rados = await cluster.client()
        await rados.pool_create("rep", pg_num=8, size=3)
        r = await rados.mon_command(
            "osd erasure-code-profile set", name="p21",
            profile={"plugin": "jax_rs", "k": "2", "m": "1",
                     "crush-failure-domain": "osd"})
        assert r["rc"] == 0, r
        await rados.pool_create("ec", pg_num=4, pool_type="erasure",
                                erasure_code_profile="p21")

        mon = cluster.mons["a"]
        m_live = mon.osd_monitor.osdmap
        pools = {p.name: pid for pid, p in m_live.pools.items()}
        await _wait_active(cluster, pools["rep"])
        await _wait_active(cluster, pools["ec"])

        payloads: dict[tuple[str, str], bytes] = {}
        rep = await rados.open_ioctx("rep")
        ec = await rados.open_ioctx("ec")
        for i in range(4):
            data = f"dr-rep-{i}-".encode() * 101
            await rep.write_full(f"obj{i}", data)
            payloads[("rep", f"obj{i}")] = data
        ecdata = bytes(range(256)) * 33                  # 8448 B
        await ec.write_full("big", ecdata)
        payloads[("ec", "big")] = ecdata

        # the live truth the offline tooling must reproduce
        m_live = mon.osd_monitor.osdmap
        epoch = m_live.epoch
        await _wait_osd_epochs(cluster, epoch)
        live = {}
        for name, pid in pools.items():
            for ps in range(m_live.pools[pid].pg_num):
                live[(pid, ps)] = m_live.pg_to_up_acting(pid, ps)

        # -- total monitor loss --------------------------------------
        await rados.shutdown()
        await cluster.stop()
        shutil.rmtree(store_dir / "mon.a")               # wiped, not
        reset_local_namespace()                          # just dead

        # -- offline surgery -----------------------------------------
        assert await objectstore_tool._run(argparse.Namespace(
            op="meta", data_path=str(store_dir / "osd.0"))) == 0
        meta = json.loads(capsys.readouterr().out)
        assert epoch in meta["osdmap_epochs"]
        assert meta["newest_epoch"] >= epoch

        argv = ["rebuild", "--store-path", str(store_dir / "mon.m"),
                "--admin-key", "dr-admin"]
        for i in range(4):
            argv += ["--osd-store", str(store_dir / f"osd.{i}")]
        assert await _tool(monstore_tool, *argv) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["osdmap_last_committed"] >= epoch

        st = MonitorDBStore.open_readonly(str(store_dir / "mon.m"))
        rebuilt_last = st.get_int("osdmap", "last_committed")
        assert rebuilt_last >= epoch
        m_off = OSDMap.from_dict(
            decode(st.get("osdmap", f"full_{epoch}")))
        assert m_off.epoch == epoch

        # --test-map-pgs bit-identity: offline simulation of the
        # harvested map == the live cluster's mapping at that epoch
        for name, pid in pools.items():
            sim = osdmaptool.map_pool_pgs(m_off, pid)
            for ps in range(m_off.pools[pid].pg_num):
                assert sim[ps] == live[(pid, ps)], \
                    f"pool {name} pg {ps}: {sim[ps]} != " \
                    f"{live[(pid, ps)]}"
        # and through the argv surface
        assert await _tool(osdmaptool, "--mon-store",
                           str(store_dir / "mon.m"),
                           "--epoch", str(epoch),
                           "--test-map-pgs") == 0
        out = json.loads(capsys.readouterr().out)
        assert out["epoch"] == epoch
        for name, pid in pools.items():
            for ps in range(m_off.pools[pid].pg_num):
                got = out["pools"][str(pid)][str(ps)]
                up, upp, acting, actp = live[(pid, ps)]
                assert got == {"up": up, "up_primary": upp,
                               "acting": acting,
                               "acting_primary": actp}

        # upmap proposals against the rebuilt map: every emitted
        # proposal must actually take when replayed through the
        # placement pipeline
        prop = osdmaptool.propose_upmaps(
            m_off, sorted(m_off.pools), deviation=0, max_proposals=6)
        work = OSDMap.from_dict(m_off.to_dict())
        for p in prop["proposals"]:       # replay the command stream
            pid_s, ps_s = p["pgid"].split(".")
            work.pg_upmap_items[(int(pid_s), int(ps_s))] = [
                tuple(pair) for pair in p["mappings"]]
            new_up, *_ = work.pg_to_up_acting(int(pid_s), int(ps_s))
            frm, to = p["mappings"][-1]   # the move this step adds
            assert frm not in new_up and to in new_up, (p, new_up)
        replayed = osdmaptool._pg_counts(work, sorted(m_off.pools))
        assert {str(k): v for k, v in sorted(replayed.items())} \
            == prop["after"]
        spread = lambda c: max(c.values()) - min(c.values())  # noqa
        assert spread(prop["after"]) <= spread(prop["before"])

        # -- new quorum + restart ------------------------------------
        conf = str(tmp_path / "cluster.json")
        assert await _tool(monmaptool, conf, "--create",
                           "--add", "m", "local://mon.m") == 0
        monmap = json.loads(
            (tmp_path / "cluster.json").read_text())["monmap"]
        assert monmap == {"m": "local://mon.m"}

        cluster2 = DevCluster(n_mons=1, n_osds=4,
                              store_dir=str(store_dir), monmap=monmap)
        await cluster2.start()
        mon2 = cluster2.mons["m"]
        # the rebuilt store skipped genesis: the map continues from
        # the harvested epoch rather than restarting at 1
        assert mon2.osd_monitor.osdmap.epoch >= epoch
        assert set(p.name for p in
                   mon2.osd_monitor.osdmap.pools.values()) \
            >= {"rep", "ec"}
        await _wait_active(cluster2, pools["rep"])
        await _wait_active(cluster2, pools["ec"])

        rados2 = await cluster2.client()
        rep2 = await rados2.open_ioctx("rep")
        ec2 = await rados2.open_ioctx("ec")
        for (pool, oid), want in payloads.items():
            ioctx = rep2 if pool == "rep" else ec2
            assert await ioctx.read(oid) == want, (pool, oid)
        await rados2.shutdown()
        await cluster2.stop()

    asyncio.run(run())


# -- satellite regressions ---------------------------------------------------
def test_mds_stale_fragtree_retry_finds_moved_name():
    """A name miss through a CACHED fragtree re-reads the tree once: a
    split since the cache fill moved the dentry into a child frag that
    exists (so no ENOENT fires the error-path retry)."""
    from ceph_tpu.mds.daemon import (MDSDaemon, MDSError, frag_for,
                                     frag_oid)

    dino, name = 0x10000000001, "moved.txt"
    # cached: one-level split; fresh: the name's leaf split again
    from ceph_tpu.placement.hashing import ceph_str_hash_rjenkins
    top1 = ceph_str_hash_rjenkins(name) >> 31
    cached = [(1, 0), (1, 1)]
    fresh = [(2, top1 * 2), (2, top1 * 2 + 1), (1, 1 - top1)]
    assert frag_for(cached, name) != frag_for(fresh, name)

    dentry = encode({"ino": 5, "type": "file"})
    omaps = {
        frag_oid(dino, *frag_for(cached, name)): {},     # stale home
        frag_oid(dino, *frag_for(fresh, name)): {name: dentry},
    }

    class _Meta:
        async def get_omap(self, oid, names=None):
            from ceph_tpu.client.rados import RadosError
            if oid not in omaps:
                raise RadosError(-2, oid)
            kv = omaps[oid]
            if names is None:
                return dict(kv)
            return {n: kv[n] for n in names if n in kv}

    class _Stub:
        meta = _Meta()
        refreshes = 0

        async def _fragtree(self, d, refresh=False):
            if refresh:
                _Stub.refreshes += 1
                return fresh
            return cached

    async def run():
        got = await MDSDaemon._get_dentry(_Stub(), dino, name)
        assert got["ino"] == 5
        assert _Stub.refreshes == 1
        # a genuinely absent name still ENOENTs (after the one refresh)
        with pytest.raises(MDSError) as ei:
            await MDSDaemon._get_dentry(_Stub(), dino, "really-gone")
        assert ei.value.missing_dentry

    asyncio.run(run())


def test_rgw_file_rename_subtree_guards():
    """rename of a directory into its own subtree is EINVAL, and
    rename-to-self is a no-op — both BEFORE the copy+delete loop that
    would otherwise destroy the tree."""
    from ceph_tpu.services.rgw import RGWLite
    from ceph_tpu.services.rgw_file import (EINVAL, FSError,
                                            RGWFileSystem)
    from tests.test_services import start_cluster, stop_cluster

    async def run():
        mon, osds, rados = await start_cluster()
        try:
            await rados.pool_create("rgwf", pg_num=8)
            ioctx = await rados.open_ioctx("rgwf")
            fs = RGWFileSystem(RGWLite(ioctx))
            await fs.mkdir("/b")
            await fs.mkdir("/b/d")
            await fs.write("/b/d/f.txt", b"payload")

            with pytest.raises(FSError) as ei:
                await fs.rename("/b/d", "/b/d/sub")
            assert ei.value.errno == EINVAL
            with pytest.raises(FSError) as ei:
                await fs.rename("/b/d", "/b/d/deeper/nest")
            assert ei.value.errno == EINVAL
            await fs.rename("/b/d", "/b/d")          # no-op, no loss
            assert await fs.read("/b/d/f.txt") == b"payload"
            # a legitimate sibling rename still works (and a name that
            # merely shares the prefix is NOT a subtree)
            await fs.mkdir("/b/dd")
            await fs.rename("/b/d", "/b/dd/moved")
            assert await fs.read("/b/dd/moved/f.txt") == b"payload"
        finally:
            await stop_cluster(mon, osds, rados)

    asyncio.run(run())


def test_rgw_push_cursor_load_backoff():
    """A transient RadosError while loading the push cursor backs off
    and retries instead of killing the delivery worker or resetting
    the cursor to 0 (which would mass-redeliver the queue)."""
    from ceph_tpu.client.rados import RadosError
    from ceph_tpu.services.rgw import RGWLite

    class _FlakyIoctx:
        calls = 0

        async def get_xattr(self, oid, name):
            _FlakyIoctx.calls += 1
            if _FlakyIoctx.calls == 1:
                raise RadosError(-110, "mon failover in progress")
            return b"7"

    gw = RGWLite.__new__(RGWLite)
    gw.ioctx = _FlakyIoctx()
    gw._pushers = {}

    async def _meta_gone(name):
        return None                   # topic deleted -> loop exits

    gw._topic_meta = _meta_gone

    async def run():
        await gw._push_loop(
            "t", {"push_endpoint": "http://127.0.0.1:1/x"},
            asyncio.Event())
        assert _FlakyIoctx.calls == 2     # retried past the transient

    asyncio.run(run())


def test_bench_refuses_a_cpu_backend():
    """bench.py times the TPU only: on the CPU backend it raises before
    any measurement instead of printing a CPU number."""
    import bench

    with pytest.raises(RuntimeError, match="not a device number"):
        bench.main()
