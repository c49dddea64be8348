"""Cross-host transport suite: real shard worker PROCESSES behind the
socket RPC front end (repro.hpo.transport, DESIGN.md §14).

Covers the fault matrix the in-process federation cannot: connection
drops mid-tell, truncated/oversized frames, heartbeat-driven death during
an in-flight migration, SIGKILL mid-`copy_study_version` — plus the
cross-deployment acceptance bar: a 2-process federation serves the same
suggestions as ONE in-process pool, bitwise (streams, ledgers, GP-state
digests, telemetry), and SIGKILL+respawn of a worker loses exactly its
uncommitted round while the survivor keeps serving."""
import asyncio
import json
import os
import signal
import socket
import struct
import tempfile

import numpy as np
import pytest

from _traffic import drive_serial, drive_serial_rpc
from _traffic import make_cfg as _cfg
from _traffic import objective as obj
from repro import checkpoint as ckpt_mod
from repro.core import GPCapacityError
from repro.hpo import (FederatedGateway, FederationConfig, GatewayConfig,
                       StudyGateway, TransportConfig, TransportError,
                       TransportFederation)
from repro.hpo import transport as tx
from repro.hpo.space import RESNET_SPACE


def _mk_tf(root, n_shards=2, slots=4, n_max=24, **tkw):
    """2-worker transport federation with test-sized budgets; health
    checks are explicit (`heartbeat_s=0`) so failover is deterministic."""
    return TransportFederation(
        RESNET_SPACE, _cfg(root, n_max=n_max),
        GatewayConfig(slots=slots),
        FederationConfig(n_shards=n_shards),
        TransportConfig(heartbeat_s=0.0, **tkw))


async def _create_on_both(tf, n=4):
    """Create n studies and sanity-check both shards got at least one
    (rendezvous placement of sids 0..n-1 — deterministic)."""
    sids = [await tf.create_study(name=f"s{i}") for i in range(n)]
    by_shard = {i: [s for s in sids if tf.shard_of(s) == i]
                for i in range(tf.fed.n_shards)}
    assert all(by_shard.values()), f"one-sided placement: {by_shard}"
    return sids, by_shard


# ---------------------------------------------------------------------------
# Frame codec (no processes)
# ---------------------------------------------------------------------------
def test_frame_roundtrip():
    msg = {"id": 7, "op": "tell",
           "args": {"sid": 3, "trial": {"unit": [0.25, 1.0]}, "value": -2.5}}
    buf = tx.encode_frame(msg)
    size = struct.unpack(">I", buf[:4])[0]
    assert size == len(buf) - 4

    async def main():
        reader = asyncio.StreamReader()
        reader.feed_data(buf)
        assert await tx.read_frame(reader) == msg
    asyncio.run(main())


def test_frame_truncation_and_oversize_are_connection_errors():
    async def main():
        # peer died mid-frame: header promises more bytes than arrive
        reader = asyncio.StreamReader()
        reader.feed_data(tx.encode_frame({"op": "ping"})[:-3])
        reader.feed_eof()
        with pytest.raises(asyncio.IncompleteReadError):
            await tx.read_frame(reader)
        # desynchronized stream: an absurd length prefix must fail before
        # any attempt to buffer it
        reader = asyncio.StreamReader()
        reader.feed_data(struct.pack(">I", 1 << 30) + b"x" * 16)
        with pytest.raises(TransportError, match="desynchronized"):
            await tx.read_frame(reader)
        # garbled body: not JSON
        reader = asyncio.StreamReader()
        reader.feed_data(struct.pack(">I", 4) + b"\xff\xfe\x00\x01")
        with pytest.raises(TransportError, match="undecodable"):
            await tx.read_frame(reader)
    asyncio.run(main())


def test_spec_roundtrip_rebuilds_the_same_gateway_shape(tmp_path):
    cfg = _cfg(str(tmp_path / "a"), n_max=24, seed=11)
    gwc = GatewayConfig(slots=3, max_inflight=2)
    spec = json.loads(json.dumps(tx.build_spec(RESNET_SPACE, cfg, gwc)))
    gw = tx.gateway_from_spec(spec, str(tmp_path / "b"))
    assert gw.cfg == _cfg(str(tmp_path / "b"), n_max=24, seed=11)
    assert gw.gw == gwc
    assert [d.name for d in gw._template_space.dims] == \
        [d.name for d in RESNET_SPACE.dims]


# ---------------------------------------------------------------------------
# Cross-deployment equivalence: 2 worker processes == 1 in-process pool
# ---------------------------------------------------------------------------
def test_spawn_refuses_workers_that_would_share_a_chip(monkeypatch,
                                                      tmp_path):
    """A chip belongs to one process: on a TPU host, spawned workers that
    would each load libtpu fail fast, before any process starts."""
    monkeypatch.setattr(tx, "tpu_chips_on_host", lambda: 4)
    tpu_env = {"JAX_PLATFORMS": ""}
    with pytest.raises(TransportError, match="4 TPU chip"):
        tx.refuse_chip_contention(tpu_env, 2)
    tx.refuse_chip_contention(tpu_env, 1)       # one worker may hold them
    tx.refuse_chip_contention({"JAX_PLATFORMS": "cpu"}, 2)  # CPU workers
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    tf = _mk_tf(str(tmp_path))

    async def main():
        with pytest.raises(TransportError, match="refusing to spawn 2"):
            await tf.start()

    asyncio.run(main())
    assert tf.procs == [None, None]
    monkeypatch.setattr(tx, "tpu_chips_on_host", lambda: 0)
    tx.refuse_chip_contention(tpu_env, 2)       # no chips: nothing to share


def test_two_process_federation_matches_single_pool_bitwise():
    """The acceptance bar of DESIGN.md §13 extended across process
    boundaries: WHERE a study is served (one pool, or 2 shard processes
    over sockets) never changes WHAT it is suggested.  Streams, ledgers,
    per-study GP-state digests, and telemetry totals must all match the
    single-pool twin bitwise."""
    async def main(root, twin_dir):
        tf = _mk_tf(os.path.join(root, "fed"))
        await tf.start()
        sids, _ = await _create_on_both(tf, 4)
        solo = StudyGateway(RESNET_SPACE, _cfg(twin_dir, n_max=24),
                            GatewayConfig(slots=8))
        assert [solo.create_study(name=f"s{i}") for i in range(4)] == sids

        st_tf = await drive_serial_rpc(tf, sids, 3)
        st_solo = await drive_serial(solo, sids, 3)
        assert st_tf == st_solo, "suggestion streams diverged"

        fed_sum = await tf.summary()
        solo_sum = solo.summary()
        assert fed_sum["asks_served"] == solo_sum["asks_served"] == 12
        assert fed_sum["absorbed"] == solo_sum["absorbed"] == 12

        stable = ("trial_id", "unit", "value", "status", "error")
        for s in sids:
            i_tf, i_solo = await tf.study_info(s), solo.study_info(s)
            assert i_tf["n_obs"] == i_solo["n_obs"] == 3
            assert i_tf["best_value"] == i_solo["best_value"]
            # ledgers: every stable field identical row for row
            led = await tf._client_for(s).call("ledger", sid=s)
            twin = solo.pool.history(solo._studies[s].slot)
            assert led is not None and len(led) == len(twin)
            for a, b in zip(led, twin):
                for k in stable:
                    assert a[k] == b[k], f"ledger[{k}] of study {s}"
            # the GP state itself, bitwise, across the process boundary
            dig = await tf._client_for(s).call("state_digest", sid=s)
            assert dig == tx.study_state_digest(
                solo.pool, solo._studies[s].slot), \
                f"study {s}: GP state diverged from the single pool"
        await tf.aclose()
        await solo.aclose()
    with tempfile.TemporaryDirectory() as root, \
            tempfile.TemporaryDirectory() as twin:
        asyncio.run(main(root, twin))


# ---------------------------------------------------------------------------
# SIGKILL + respawn: the federation-level crash acceptance bar
# ---------------------------------------------------------------------------
def test_sigkill_respawn_loses_exactly_the_uncommitted_round():
    """SIGKILL one worker process mid-traffic: the survivor keeps serving
    without a hiccup, and the respawned process comes back at its last
    committed epoch — the uncommitted round is lost, nothing pre-crash
    replays, and the retried round re-derives the lost suggestions
    bitwise from the persisted PRNG streams."""
    async def main(root):
        tf = _mk_tf(root)
        await tf.start()
        sids, by_shard = await _create_on_both(tf, 4)
        victim = tf.shard_of(sids[0])
        survivor = 1 - victim

        pre = await drive_serial_rpc(tf, sids, 2)
        await tf.checkpoint()                 # commits round 1-2
        lost = await drive_serial_rpc(tf, sids, 1)   # round 3: uncommitted

        pid = tf.procs[victim].pid
        tf.kill_shard(victim)                 # real SIGKILL
        with pytest.raises(ProcessLookupError):
            os.kill(pid, 0)

        # the survivor is undisturbed mid-crash
        s_surv = by_shard[survivor][0]
        tr = await tf.ask(s_surv)
        await tf.tell(s_surv, tr, obj(s_surv, tr.unit))
        await tf.drain()
        assert (await tf.study_info(s_surv))["n_obs"] == 4

        await tf.revive_shard(victim)
        for s in by_shard[victim]:
            assert (await tf.study_info(s))["n_obs"] == 2, \
                "a committed tell was lost in the crash"

        post = await drive_serial_rpc(tf, sids, 2)
        for s in sids:
            assert set(pre[s]).isdisjoint(post[s]), \
                "revived worker replayed a pre-crash suggestion"
            if tf.shard_of(s) == victim:
                assert post[s][0] == lost[s][0], \
                    "the lost round did not re-derive bitwise"
        await tf.aclose()
    with tempfile.TemporaryDirectory() as root:
        asyncio.run(main(root))


# ---------------------------------------------------------------------------
# Fault matrix: dropped connections, parked asks, garbage frames
# ---------------------------------------------------------------------------
def test_connection_faults_cancel_asks_fail_tells_survive_garbage():
    """One federation, three faults.  A worker SIGKILLed with calls in
    flight: the parked ask future CANCELS (kill_shard semantics — the
    client re-asks later) while the in-flight tell fails LOUDLY with
    ShardConnectionError (a lost result must never vanish silently).
    Garbage frames on a raw socket must not disturb the worker.  The
    revived worker then serves both studies again."""
    async def main(root):
        tf = _mk_tf(root)
        await tf.start()
        sids, by_shard = await _create_on_both(tf, 4)
        victim = tf.shard_of(sids[0])
        survivor = 1 - victim
        s_vic = by_shard[victim][0]
        s_surv = by_shard[survivor][0]
        await drive_serial_rpc(tf, sids, 1)
        await tf.checkpoint()

        # hold a live suggestion, then freeze the worker so the next
        # calls park on the wire
        held = await tf.ask(s_vic)
        os.kill(tf.procs[victim].pid, signal.SIGSTOP)
        ask_fut = asyncio.ensure_future(tf.ask(s_vic))
        tell_fut = asyncio.ensure_future(
            tf.tell(s_vic, held, obj(s_vic, held.unit)))
        await asyncio.sleep(0.3)              # both frames sent, parked
        assert not ask_fut.done() and not tell_fut.done()
        tf.kill_shard(victim)                 # SIGKILL severs the socket
        with pytest.raises(asyncio.CancelledError):
            await ask_fut
        with pytest.raises(tx.ShardConnectionError):
            await tell_fut
        # routed calls to a dead shard fail fast until revival
        with pytest.raises(RuntimeError, match="down"):
            await tf.ask(s_vic)

        # garbage on a raw socket: truncated frame, then an absurd length
        # prefix — the SURVIVOR worker must shrug both off
        with open(os.path.join(tf.shard_dir(survivor),
                               tx.ENDPOINT_FILE)) as f:
            ep = json.load(f)
        for garbage in (struct.pack(">I", 100) + b"short",
                        struct.pack(">I", 1 << 30) + b"x" * 32):
            raw = socket.create_connection((ep["host"], ep["port"]))
            raw.sendall(garbage)
            raw.close()
        tr = await tf.ask(s_surv)
        await tf.tell(s_surv, tr, obj(s_surv, tr.unit))
        await tf.drain()

        await tf.revive_shard(victim)
        # the held suggestion died with the worker's outstanding map and
        # its tell never committed: the study is back at the epoch
        assert (await tf.study_info(s_vic))["n_obs"] == 1
        tr = await tf.ask(s_vic)
        await tf.tell(s_vic, tr, obj(s_vic, tr.unit))
        await tf.drain()
        assert (await tf.study_info(s_vic))["n_obs"] == 2
        await tf.aclose()
    with tempfile.TemporaryDirectory() as root:
        asyncio.run(main(root))


def test_tell_replay_and_capacity_errors_cross_the_wire():
    """Error types that are part of the gateway contract must round-trip
    the RPC boundary: a replayed tell raises the same RuntimeError as
    in-process, and an impossible ask width raises GPCapacityError."""
    async def main(root):
        tf = _mk_tf(root)
        await tf.start()
        sid = await tf.create_study(name="s")
        tr = await tf.ask(sid)
        await tf.tell(sid, tr, 0.5)
        with pytest.raises(RuntimeError, match="exactly one tell"):
            await tf.tell(sid, tr, 0.5)
        # ... and the server-side outstanding map catches a replay even
        # when the client-side status is forged back
        tr.status = "running"
        with pytest.raises(RuntimeError, match="exactly one tell"):
            await tf.tell(sid, tr, 0.5)
        with pytest.raises(GPCapacityError, match="max_inflight"):
            await tf.ask(sid, q=99)
        with pytest.raises(KeyError, match="unknown study"):
            await tf.ask(777)
        await tf.aclose()
    with tempfile.TemporaryDirectory() as root:
        asyncio.run(main(root))


# ---------------------------------------------------------------------------
# Heartbeat flap during an in-flight migration
# ---------------------------------------------------------------------------
def test_heartbeat_flap_mid_migration_aborts_all_or_nothing():
    """The destination worker stops answering (SIGSTOP) with an adopt RPC
    in flight: health checks mark it dead at miss_limit, the migration
    aborts LOUDLY, and the study is fully intact on its source shard —
    adopt-before-detach means no fault before the final detach can lose
    it.  After revival the SAME migration retries to completion (the
    copy is idempotent on a committed version)."""
    async def main(root):
        tf = _mk_tf(root, heartbeat_timeout_s=0.25, miss_limit=2)
        await tf.start()
        sids, _ = await _create_on_both(tf, 4)
        sid = sids[0]
        src = tf.shard_of(sid)
        dst = 1 - src
        await drive_serial_rpc(tf, sids, 2)

        os.kill(tf.procs[dst].pid, signal.SIGSTOP)
        mig = asyncio.ensure_future(tf.migrate_study(sid, dst))
        await asyncio.sleep(0.4)   # export+copy done, adopt parked on dst
        died = []
        for _ in range(4):
            died += await tf.check_health()
            if dst in died:
                break
        assert dst in died, "flapping shard was never marked dead"
        with pytest.raises(RuntimeError):   # ShardConnectionError or
            await mig                        # routed-to-dead, both loud
        # all-or-nothing: still owned and servable on the source
        assert tf.shard_of(sid) == src
        tr = await tf.ask(sid)
        await tf.tell(sid, tr, obj(sid, tr.unit))
        await tf.drain()
        assert (await tf.study_info(sid))["n_obs"] == 3

        os.kill(tf.procs[dst].pid, signal.SIGCONT)
        await tf.revive_shard(dst)   # kills the zombie first, respawns
        await tf.migrate_study(sid, dst)
        assert tf.shard_of(sid) == dst
        info = await tf.study_info(sid)
        assert info["n_obs"] == 3 and info["shard"] == dst
        tr = await tf.ask(sid)
        await tf.tell(sid, tr, obj(sid, tr.unit))
        await tf.drain()
        assert (await tf.study_info(sid))["n_obs"] == 4
        await tf.aclose()
    with tempfile.TemporaryDirectory() as root:
        asyncio.run(main(root))


# ---------------------------------------------------------------------------
# SIGKILL during copy_study_version: no debris is ever adoptable
# ---------------------------------------------------------------------------
def _copy_then_die(src, dst, key, version):
    """Child process: SIGKILL itself after the first snapshot file lands
    in the migration staging dir — a front end dying mid-copy."""
    from repro.checkpoint import store as store_mod
    real = store_mod.shutil.copy2

    def die_after_one(a, b):
        real(a, b)
        os.kill(os.getpid(), signal.SIGKILL)
    store_mod.shutil.copy2 = die_after_one
    store_mod.copy_study_version(src, dst, key, version)


def test_sigkill_during_copy_leaves_no_adoptable_debris():
    """A SIGKILLed copier leaves only `.tmp_migrate_*` staging debris on
    the destination — never a COMMITTED version.  Adoption refuses the
    record, the age-guarded sweep reclaims the debris, and the retried
    copy publishes cleanly (all-or-nothing, DESIGN.md §14)."""
    import multiprocessing as mp
    with tempfile.TemporaryDirectory() as src_d, \
            tempfile.TemporaryDirectory() as dst_d:
        async def seed(d):
            gw = StudyGateway(RESNET_SPACE, _cfg(d), GatewayConfig(slots=2))
            sid = gw.create_study()
            tr = await gw.ask(sid)
            gw.tell(sid, tr, obj(sid, tr.unit))
            await gw.drain()
            record = gw.export_for_migration(sid)   # commits version 1
            await gw.aclose()
            return record
        record = asyncio.run(seed(src_d))
        key, version = record["key"], record["version"]
        assert version in ckpt_mod.study_versions(src_d, key)

        ctx = mp.get_context("spawn")
        p = ctx.Process(target=_copy_then_die,
                        args=(src_d, dst_d, key, version), daemon=True)
        p.start()
        p.join(timeout=120)
        assert p.exitcode == -signal.SIGKILL

        sdir = ckpt_mod.study_dir(dst_d, key)
        debris = [f for f in os.listdir(sdir)
                  if f.startswith(".tmp_migrate_")]
        assert debris, "the SIGKILL arrived after publication?"
        # nothing committed -> a migration-grade adopt refuses the record
        assert not ckpt_mod.study_versions(dst_d, key)
        dst_gw = StudyGateway(RESNET_SPACE, _cfg(dst_d),
                              GatewayConfig(slots=2))
        with pytest.raises(RuntimeError, match="not.*committed"):
            dst_gw.adopt_study(record)
        # age-guarded sweep: fresh debris survives the default TTL, a
        # zero-TTL sweep (or an aged mtime) reclaims it
        assert ckpt_mod.sweep_tmp(sdir) == []
        swept = ckpt_mod.sweep_tmp(sdir, ttl_s=0.0)
        assert [os.path.basename(s) for s in swept] == debris
        # the retry publishes, and the adopt goes through
        ckpt_mod.copy_study_version(src_d, dst_d, key, version)
        assert version in ckpt_mod.study_versions(dst_d, key)
        dst_gw.adopt_study(record)
        assert dst_gw.study_info(int(record["sid"]))["n_obs"] == 1


# ---------------------------------------------------------------------------
# Multi-process soak (REPRO_SOAK gate, like tests/test_soak.py)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(not os.environ.get("REPRO_SOAK"),
                    reason="multi-process fault soak; set REPRO_SOAK=1 "
                           "(dedicated CI job runs it)")
def test_soak_transport_twin_of_inmemory_federation_under_faults():
    """Long-haul twin run: a 2-process TransportFederation and the
    in-memory FederatedGateway driven through the SAME trace with the
    SAME fault schedule (checkpoint every 3 rounds, SIGKILL/revive of
    one shard mid-run) must stay bitwise twins end to end — streams,
    n_obs, best values.  The survivor serves through the crash; the
    revived process loses exactly the uncommitted round on both sides."""
    async def main(root_tf, root_fg):
        tf = _mk_tf(root_tf, slots=3, n_max=64)
        await tf.start()
        fg = FederatedGateway(RESNET_SPACE, _cfg(root_fg, n_max=64),
                              GatewayConfig(slots=3),
                              FederationConfig(n_shards=2))
        sids, by_shard = await _create_on_both(tf, 6)
        assert [fg.create_study(name=f"s{i}") for i in range(6)] == sids
        victim = tf.shard_of(sids[0])

        st_tf, st_fg = {s: [] for s in sids}, {s: [] for s in sids}
        for r in range(12):
            await drive_serial_rpc(tf, sids, 1, streams=st_tf)
            await drive_serial(fg, sids, 1, streams=st_fg)
            if r % 3 == 2:
                await tf.checkpoint()
                fg.checkpoint()
            if r == 6:
                tf.kill_shard(victim)
                fg.kill_shard(victim)
                # survivors keep serving mid-crash on both deployments
                s_surv = by_shard[1 - victim][0]
                tr = await tf.ask(s_surv)
                await tf.tell(s_surv, tr, obj(s_surv, tr.unit))
                await tf.drain()
                tr2 = await fg.ask(s_surv)
                fg.tell(s_surv, tr2, obj(s_surv, tr2.unit))
                await fg.drain()
                assert tuple(np.asarray(tr.unit).tolist()) == \
                    tuple(np.asarray(tr2.unit).tolist())
                st_tf[s_surv].append(tuple(np.asarray(tr.unit).tolist()))
                st_fg[s_surv].append(tuple(np.asarray(tr2.unit).tolist()))
                await tf.revive_shard(victim)
                fg.revive_shard(victim)
                # the uncommitted round is gone on BOTH: re-derive it
                for s in by_shard[victim]:
                    assert (await tf.study_info(s))["n_obs"] == \
                        fg.study_info(s)["n_obs"]
        assert st_tf == st_fg, "transport diverged from in-memory twin"
        for s in sids:
            i_tf, i_fg = await tf.study_info(s), fg.study_info(s)
            assert i_tf["n_obs"] == i_fg["n_obs"]
            assert i_tf["best_value"] == i_fg["best_value"]
        fed_sum, solo_sum = await tf.summary(), fg.summary()
        assert fed_sum["asks_served"] == solo_sum["asks_served"]
        assert fed_sum["absorbed"] == solo_sum["absorbed"]
        await tf.aclose()
        await fg.aclose()
    with tempfile.TemporaryDirectory() as a, \
            tempfile.TemporaryDirectory() as b:
        asyncio.run(main(a, b))
