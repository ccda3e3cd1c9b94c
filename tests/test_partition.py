"""Partition-tolerant membership: epoch fencing, net chaos, Jepsen soak.

The done-criteria of the partition PR:
  (a) no silent resurrection: a heartbeat from a dead-marked node is
      NACKed with typed StaleNodeEpochError (never flips alive in
      place), and stale-epoch RPCs are rejected the same way;
  (b) the net.* chaos points (rpc call/connect) and the group-based
      chaos.partition API inject real control-plane partitions —
      seeded, flight-recorded, counted;
  (c) the partition acceptance e2e: isolate a worker from the GCS while
      its named actor keeps running -> dead + rescheduled -> heal ->
      zombie fenced, workers killed, fresh-epoch rejoin — with the
      exactly-once counter audit and the flight-ring ordering
      chaos.partition <= node.dead <= node.fence <= node.added;
  (d) partition-vs-collective (mid-op timeout naming missing ranks, not
      a hang) and partition-vs-cgraph (ChannelClosed -> elastic
      re-form);
  (e) a bounded seeded soak (tools/chaos_soak.py) in tier-1.
"""

import os
import socket
import threading
import time
import uuid

import pytest

import ray_tpu as rt
from ray_tpu import chaos
from ray_tpu import exceptions as exc
from ray_tpu.core import runtime_base
from ray_tpu.core.cluster_runtime import Cluster

pytestmark = pytest.mark.chaos

SOAK_SEED = int(os.environ.get("RAY_TPU_CHAOS_SEED", "1030") or 1030)


def _wait_for(pred, timeout=20.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


# ================================ (a) fencing units (in-process GcsService)
def test_heartbeat_from_dead_node_fenced_not_resurrected():
    """The silent-resurrection regression: a dead-marked node's heartbeat
    must NOT flip it back alive in place — it gets the typed fence error,
    the FENCED state, and the raytpu_nodes_fenced_total bump; only a
    fresh register_node (new epoch) rejoins it."""
    from ray_tpu.core.gcs import GcsService

    svc = GcsService()
    try:
        reg = svc.register_node("nodeA", "/tmp/nope.sock", "/tmp/nope", {"CPU": 2.0})
        assert reg["epoch"] == 1
        svc.drain_node("nodeA")  # declared dead (heartbeat expiry analogue)

        with pytest.raises(exc.StaleNodeEpochError) as ei:
            svc.heartbeat("nodeA", {"CPU": 2.0}, None, 1)
        assert ei.value.node_id == "nodeA"
        nodes = {n["NodeID"]: n for n in svc.list_nodes()}
        assert nodes["nodeA"]["Alive"] is False  # never resurrected in place
        assert nodes["nodeA"]["Fenced"] is True
        assert nodes["nodeA"]["State"] == "FENCED"

        # Epoch-less legacy heartbeat from a dead node: same rejection.
        with pytest.raises(exc.StaleNodeEpochError):
            svc.heartbeat("nodeA", {"CPU": 2.0})

        # The only way back in: a fresh registration with a new epoch.
        reg2 = svc.register_node("nodeA", "/tmp/nope.sock", "/tmp/nope", {"CPU": 2.0})
        assert reg2["epoch"] == 2
        nodes = {n["NodeID"]: n for n in svc.list_nodes()}
        assert nodes["nodeA"]["State"] == "ALIVE" and nodes["nodeA"]["Epoch"] == 2

        # A stale-epoch heartbeat (the OLD incarnation) is fenced even
        # though the node id is alive again.
        with pytest.raises(exc.StaleNodeEpochError):
            svc.heartbeat("nodeA", {"CPU": 2.0}, None, 1)
        assert svc.heartbeat("nodeA", {"CPU": 2.0}, None, 2)["ok"] is True
    finally:
        svc.stop()


def test_gcs_own_stall_is_not_charged_to_nodes(monkeypatch):
    """The failure detector pauses (a TPU runtime start-up freezes every
    process of a sandboxed host for ~8 s): on waking it must not find every
    heartbeat stale and fence the node — it credits its own pause. A node
    that stays silent afterwards still dies."""
    from ray_tpu.core import gcs as gcs_mod
    from ray_tpu.core.gcs import GcsService

    monkeypatch.setattr(gcs_mod, "HEARTBEAT_TIMEOUT_S", 0.6)
    svc = GcsService()
    try:
        svc.register_node("nodeS", "/tmp/s.sock", "/tmp/s", {"CPU": 1.0})
        stalled = threading.Event()
        real = svc._process_frees

        def freeze_once():
            if not stalled.is_set():
                stalled.set()
                time.sleep(1.5)  # the health loop stands still, past the timeout
            real()

        monkeypatch.setattr(svc, "_process_frees", freeze_once)
        assert stalled.wait(5)
        time.sleep(1.7)  # the loop is back and has run its liveness check
        assert svc.heartbeat("nodeS", {"CPU": 1.0})["ok"] is True  # not fenced
        alive = {n["NodeID"]: n["Alive"] for n in svc.list_nodes()}
        assert alive["nodeS"] is True
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and alive["nodeS"]:
            time.sleep(0.1)
            alive = {n["NodeID"]: n["Alive"] for n in svc.list_nodes()}
        assert alive["nodeS"] is False  # silence beyond the timeout still kills
    finally:
        svc.stop()


def test_stale_epoch_rejected_on_mutation_rpcs():
    from ray_tpu.core.gcs import GcsService

    svc = GcsService()
    try:
        svc.register_node("nodeB", "/tmp/b.sock", "/tmp/b", {"CPU": 1.0})
        svc.drain_node("nodeB")
        with pytest.raises(exc.StaleNodeEpochError):
            svc.node_sync("nodeB", ["ab" * 12], [], 1)
        with pytest.raises(exc.StaleNodeEpochError):
            svc.actor_started("actorX", "nodeB", 1)
        with pytest.raises(exc.StaleNodeEpochError):
            svc.remove_object_location("ab" * 12, "nodeB", 1)
        # The zombie's sealed objects never entered the directory.
        assert svc.get_object_locations("ab" * 12) == []
        # Unknown nodes pass through (legacy/driver callers).
        assert svc.node_sync("never_registered", [], [], None) is True
    finally:
        svc.stop()


def test_stale_node_epoch_error_pickles_with_fields():
    import pickle

    err = exc.StaleNodeEpochError("n1", 3, 5, "heartbeat")
    back = pickle.loads(pickle.dumps(err))
    assert back.node_id == "n1" and back.claimed_epoch == 3
    assert back.current_epoch == 5 and isinstance(back, ConnectionError)


# ======================================= (b) net.* chaos + partition units
def test_net_call_drop_rule_typed_error(tmp_path):
    """A seeded net.call drop rule black-holes a two-way call: typed
    RpcUnavailableError, no hang (the server is alive and reachable)."""
    from ray_tpu.core.gcs import GcsService
    from ray_tpu.core.rpc import RpcClient, RpcServer

    svc = GcsService()
    server = RpcServer(str(tmp_path / "gcs.sock"), svc)
    try:
        cli = RpcClient(server.address)
        assert cli.call("ping") == "pong"
        chaos.configure(
            [{"point": "net.call", "action": "drop", "match": "ping", "times": 1}],
            seed=0,
        )
        with pytest.raises(exc.RpcUnavailableError):
            cli.call("ping")
        assert cli.call("ping") == "pong"  # times=1: next call flows
    finally:
        chaos.disable()
        svc.stop()
        server.shutdown()


def test_net_connect_drop_burns_deadline(tmp_path):
    from ray_tpu.core.gcs import GcsService
    from ray_tpu.core.rpc import RpcClient, RpcServer

    svc = GcsService()
    server = RpcServer(str(tmp_path / "gcs2.sock"), svc)
    try:
        chaos.configure(
            [{"point": "net.connect", "action": "drop", "times": -1}], seed=0
        )
        t0 = time.monotonic()
        with pytest.raises(exc.RpcUnavailableError):
            RpcClient(server.address, connect_timeout=0.5)
        elapsed = time.monotonic() - t0
        assert 0.4 <= elapsed < 5.0  # burned its own deadline, no instant fail
    finally:
        chaos.disable()
        svc.stop()
        server.shutdown()


def test_partition_module_units(tmp_path):
    from ray_tpu.chaos import net as netpart

    assert not netpart.active()
    netpart.install(["raylet_abc"], heal_after=None, spec_id="t1")
    try:
        assert netpart.active()
        assert netpart.blocked_addr("/tmp/s/raylet_abc.sock") == "raylet_abc"
        assert netpart.blocked_addr("/tmp/s/raylet_xyz.sock") is None
    finally:
        assert netpart.heal("t1")
    assert not netpart.active()

    # Deadline self-heal: every process enforces its own clock.
    netpart.install(["raylet_abc"], heal_after=0.2, spec_id="t2")
    try:
        assert netpart.blocked_addr("raylet_abc") is not None
        time.sleep(0.3)
        assert netpart.blocked_addr("raylet_abc") is None
        assert not netpart.active()
    finally:
        netpart.heal("t2")

    # Overlapping specs stack: a second install must not lift the first
    # (a chaos campaign routinely partitions two victims through the
    # same GCS process), and each heals independently.
    netpart.install(["raylet_one"], spec_id="o1")
    netpart.install(["raylet_two"], spec_id="o2")
    try:
        assert netpart.blocked_addr("raylet_one.sock") == "raylet_one"
        assert netpart.blocked_addr("raylet_two.sock") == "raylet_two"
        assert netpart.heal("o1")
        assert netpart.blocked_addr("raylet_one.sock") is None
        assert netpart.blocked_addr("raylet_two.sock") == "raylet_two"
    finally:
        netpart.heal()  # heal-all
    assert not netpart.active()


def test_partition_api_validation():
    with pytest.raises((ValueError, RuntimeError)):
        chaos.partition([["only_one_group"]])


# =========================================== (c) the acceptance e2e
def _define_counter():
    @rt.remote(max_restarts=-1, resources={"ctr": 0.5})
    class PartCounter:
        def incr(self, op_id):
            import os as _os
            import uuid as _uuid

            from ray_tpu.core.runtime_base import current_runtime

            current_runtime()._gcs.call(
                "kv_put",
                f"partctr/{op_id}/{_os.getpid()}-{_uuid.uuid4().hex[:6]}",
                b"1",
            )
            return True

        def whereami(self):
            import os as _os

            return _os.getpid()

    return PartCounter


def test_partition_acceptance_e2e(tmp_path, monkeypatch):
    """Partition a worker from the GCS for > heartbeat timeout while its
    named actor keeps running: the GCS declares it dead and reschedules
    the actor; on heal the zombie's first RPC is fenced
    (StaleNodeEpochError), its workers die, and it rejoins with a new
    epoch. The invariant checker proves exactly one live named-actor
    instance post-heal and no lost/duplicated counter increments across
    the whole timeline; the flight ring orders
    chaos.partition <= node.dead <= node.fence <= node.added."""
    from ray_tpu.observability import flight_recorder as frec
    from ray_tpu.observability import perfetto
    from ray_tpu.utils import state

    monkeypatch.setenv("RAY_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setenv("RAY_TPU_HEARTBEAT_INTERVAL_S", "0.25")
    monkeypatch.setenv("RAY_TPU_HEARTBEAT_TIMEOUT_S", "1.5")
    rt.shutdown()
    cluster = Cluster(num_cpus=2)
    runtime = cluster.runtime()
    runtime_base.set_runtime(runtime)
    stop = threading.Event()
    acked, errored = set(), set()
    try:
        workers = [
            cluster.add_node(num_cpus=2, resources={"ctr": 1.0})
            for _ in range(2)
        ]
        gcs = runtime._gcs
        counter = _define_counter().options(name="part_ctr").remote()
        zombie_pid = rt.get(counter.whereami.remote(), timeout=30)

        def actor_node():
            for a in state.list_actors():
                if a.get("name") == "part_ctr" and a["state"] == "ALIVE":
                    return a.get("node_id")
            return None

        victim = actor_node()
        assert victim in workers

        def client():
            while not stop.is_set():
                op = uuid.uuid4().hex[:12]
                try:
                    rt.get(counter.incr.remote(op), timeout=20)
                    acked.add(op)
                except Exception:
                    errored.add(op)
                    time.sleep(0.2)
                time.sleep(0.03)

        t = threading.Thread(target=client, daemon=True)
        t.start()
        assert _wait_for(lambda: len(acked) >= 5, timeout=30)

        def node(nid):
            return {n["NodeID"]: n for n in gcs.call("list_nodes")}[nid]

        # ---- partition the victim's raylet from the GCS (driver + data
        # plane stay connected: the actor KEEPS RUNNING as a zombie).
        p = chaos.partition([[victim], ["gcs"]], heal_after=60.0, runtime=runtime)
        assert _wait_for(lambda: not node(victim)["Alive"], timeout=20), (
            "partitioned node never declared dead"
        )
        # The zombie raylet process is still running (not crashed).
        os.kill(cluster._node_procs[victim].pid, 0)
        # The GCS rescheduled the named actor onto the surviving worker.
        assert _wait_for(
            lambda: actor_node() not in (None, victim), timeout=30
        ), "named actor was not rescheduled off the dead node"

        # ---- heal: the zombie's first heartbeat is fenced; its workers
        # die; it rejoins as a fresh epoch.
        old_epoch = node(victim)["Epoch"]
        p.heal()
        assert _wait_for(
            lambda: node(victim)["Alive"]
            and node(victim)["Epoch"] == old_epoch + 1,
            timeout=30,
        ), f"no fresh-epoch rejoin: {node(victim)['State']}"
        # The zombie instance was killed by the fence.
        assert _wait_for(
            lambda: not os.path.exists(f"/proc/{zombie_pid}"), timeout=20
        ), "zombie actor instance survived the fence"

        # ---- invariants across the whole timeline.
        stop.set()
        t.join(timeout=60)
        alive_records = [
            a
            for a in state.list_actors()
            if a.get("name") == "part_ctr" and a["state"] == "ALIVE"
        ]
        assert len(alive_records) == 1, alive_records
        final_pid = rt.get(counter.whereami.remote(), timeout=60)
        assert final_pid != zombie_pid

        applied = {}
        for key in gcs.call("kv_keys", "partctr/"):
            op = key[len("partctr/"):].split("/", 1)[0]
            applied[op] = applied.get(op, 0) + 1
        lost = [op for op in acked if applied.get(op, 0) == 0]
        duped = [op for op, n in applied.items() if n > 1]
        phantom = [op for op in applied if op not in acked | errored]
        assert not lost, f"acked increments lost: {lost[:5]}"
        assert not duped, f"increments double-applied: {duped[:5]}"
        assert not phantom, f"phantom increments: {phantom[:5]}"

        def fenced_total():
            return sum(
                m["value"]
                for m in state.internal_metrics()
                if m["name"] == "raytpu_nodes_fenced_total"
            )

        # Poll: the GCS flushes its own counters on a ~1 s cadence, and
        # under CI load the read can race the flush.
        assert _wait_for(lambda: fenced_total() >= 1, timeout=15)

        # ---- flight-ring ordering: the GCS ring alone holds the whole
        # membership story (partition install RPC, death, fence, rejoin).
        gcs.call("flight_dump")
        frec.dump(reason="test: partition acceptance")
        all_events = perfetto.flight_events(
            frec.collect(str(tmp_path / "flight"))
        )
        # This partition's story only: node.* records carry the victim's
        # node-id prefix, the install record carries the spec id (boot
        # noise — e.g. a transient heartbeat miss under CI load — may put
        # unrelated membership events in the ring).
        events = [
            e
            for e in all_events
            if (
                e["name"].startswith("node.")
                and victim[:12] in e["args"]["detail"]
            )
            or (e["name"] == "chaos.partition" and p.spec_id in e["args"]["detail"])
        ]
        names = {e["name"] for e in events}
        for expected in ("chaos.partition", "node.dead", "node.fence", "node.added"):
            assert expected in names, f"{expected} missing from {sorted(names)}"

        def first_ts(name):
            return min(e["ts"] for e in events if e["name"] == name)

        def last_ts(name):
            return max(e["ts"] for e in events if e["name"] == name)

        assert (
            first_ts("chaos.partition")
            <= first_ts("node.dead")
            <= first_ts("node.fence")
            <= last_ts("node.added")
        )
    finally:
        stop.set()
        rt.shutdown()


# ============================== (d) partition vs collective / cgraph
def test_collective_mid_op_partition_times_out_naming_ranks(monkeypatch):
    """A one-way stall mid-op (rank 1's op delayed past the op deadline —
    what a one-way partition of the ring looks like to rank 0) must
    surface CollectiveTimeoutError NAMING the stalled rank, not hang."""
    rules = [
        {
            "point": "coll.op",
            "action": "delay",
            "match": "allreduce:pgrp:1",
            "delay_s": 15.0,
            "times": 1,
        }
    ]
    import json

    monkeypatch.setenv("RAY_TPU_COLLECTIVE_TIMEOUT_S", "2.0")
    # The mid-op deadline is its own (much larger by default) knob so a
    # healthy straggler's long compile can't kill a gang at rendezvous
    # speed; the chaos test shrinks both.
    monkeypatch.setenv("RAY_TPU_COLLECTIVE_OP_TIMEOUT_S", "2.0")
    monkeypatch.setenv(chaos.ENV_VAR, json.dumps(rules))
    monkeypatch.setenv(chaos.SEED_ENV, str(SOAK_SEED))
    rt.shutdown()
    rt.init(num_cpus=4, num_workers=2)
    try:
        from ray_tpu import collective

        @rt.remote
        class Member:
            def reduce(self, v):
                import numpy as _np

                from ray_tpu import collective as coll
                from ray_tpu import exceptions as _exc

                try:
                    return (
                        "ok",
                        float(coll.allreduce(_np.array([v]), "pgrp")[0]),
                    )
                except _exc.CollectiveTimeoutError as e:
                    return ("timeout", e.group, e.rank, list(e.missing))

            def ping(self):
                return True

        members = [Member.remote() for _ in range(2)]
        rt.get([m.ping.remote() for m in members], timeout=60)
        collective.create_collective_group(members, "pgrp")
        t0 = time.monotonic()
        refs = [m.reduce.remote(float(i + 1)) for i, m in enumerate(members)]
        r0 = rt.get(refs[0], timeout=60)
        assert r0[0] == "timeout", f"rank 0 did not time out: {r0}"
        assert r0[1] == "pgrp" and r0[2] == 0 and 1 in r0[3]
        assert time.monotonic() - t0 < 12.0  # typed error, not a hang
        try:
            rt.get(refs[1], timeout=60)  # drain (delayed, then peer gone)
        except Exception:
            pass
    finally:
        rt.shutdown()


def test_cgraph_member_partition_channel_closed_elastic_reform(monkeypatch):
    """A cgraph member on a GCS-partitioned node: the gang member is
    declared dead, the heal-time fence kills its worker (exec loop dies
    -> ChannelClosed), and ElasticGraph re-forms at the survivors."""
    from ray_tpu import cgraph
    from ray_tpu.dag import InputNode, MultiOutputNode

    monkeypatch.setenv("RAY_TPU_HEARTBEAT_INTERVAL_S", "0.25")
    monkeypatch.setenv("RAY_TPU_HEARTBEAT_TIMEOUT_S", "1.5")
    rt.shutdown()
    cluster = Cluster(num_cpus=2)
    runtime = cluster.runtime()
    runtime_base.set_runtime(runtime)
    try:
        node_a = cluster.add_node(num_cpus=2, resources={"sa": 1.0})
        node_b = cluster.add_node(num_cpus=2, resources={"sb": 1.0})

        @rt.remote(max_restarts=0)
        class Stage:
            def apply(self, x):
                return x + 1

            def ping(self):
                return True

        a = Stage.options(resources={"sa": 0.5}).remote()
        b = Stage.options(resources={"sb": 0.5}).remote()
        rt.get([a.ping.remote(), b.ping.remote()], timeout=60)

        def build(actors):
            with InputNode() as inp:
                outs = [m.apply.bind(inp) for m in actors]
                return MultiOutputNode(outs)

        eg = cgraph.ElasticGraph(build, [a, b], min_actors=1, rebuild_timeout=90.0)
        assert eg.run(1, timeout=30) == [2, 2]
        assert eg.world_size == 2

        p = chaos.partition([[node_b], ["gcs"]], heal_after=45.0, runtime=runtime)

        def b_dead():
            from ray_tpu.utils import state

            return any(
                x["actor_id"] == b._actor_id.hex() and x["state"] == "DEAD"
                for x in state.list_actors()
            )

        assert _wait_for(b_dead, timeout=30), "partitioned member never marked DEAD"
        p.heal()  # fence kills b's worker -> exec loop dies -> ChannelClosed
        deadline = time.monotonic() + 60
        while True:
            out = eg.run(5, timeout=15)
            if eg.world_size == 1:
                assert out == [6]
                break
            assert time.monotonic() < deadline, "elastic graph never re-formed"
            time.sleep(0.3)
        eg.teardown()
    finally:
        rt.shutdown()


# ===================================== (e) the bounded tier-1 soak
def test_partition_soak_tier1():
    """60-second seeded membership soak (tools/chaos_soak.py): randomized
    partition/heal/kill/preempt against named actors + a counter + a task
    workload, exactly-once and singleton invariants checked throughout.
    RAY_TPU_CHAOS_SEED pins the campaign; failures print the event log."""
    from tools.chaos_soak import run_soak

    rt.shutdown()
    result = run_soak(SOAK_SEED, 45.0, nodes=2, event_period_s=1.5)
    assert result.ok, f"soak violations: {result.summary()}\n{result.events}"
    assert len(result.ops_acked) > 50, result.summary()
    assert result.task_rounds > 10, result.summary()
