"""Gang replicas (tfmesos_tpu/fleet/gang.py + the scheduler/registry/
launcher halves) — all jax-free: the leader/member wire protocol over
real WireServer sockets (join fencing, dispatch/digest acks, member
EOF = gang break), the registry's gang heartbeat field + gang_lookup
rendezvous + gang gauges, the scheduler's atomic gang placement and
per-member env stamping, and the per-replica rid seeding that closes
the PR 4 cross-exporter rid-collision caveat.  The full-process gang
e2e (2-member gang behind the gateway, token identity, member SIGKILL,
drain migration) is the slow-marked smoke in test_fleet_scenarios.py."""

import threading
import time

import pytest

from tfmesos_tpu import wire
from tfmesos_tpu.fleet.gang import (GANG_ENV_ID, GANG_ENV_RANK,
                                    GANG_ENV_SIZE, GangLeader, GangMember,
                                    leader_handler, read_gang_env,
                                    token_digest)
from tfmesos_tpu.fleet.registry import ReplicaRegistry


# -- env contract + digests --------------------------------------------------


def test_read_gang_env_contract():
    env = {GANG_ENV_ID: "replica/g1", GANG_ENV_SIZE: "4",
           GANG_ENV_RANK: "2"}
    assert read_gang_env(env) == ("replica/g1", 4, 2)
    # No gang id: the single-process replica of old.
    assert read_gang_env({}) is None
    # Malformed values degrade to no-gang, never crash.
    assert read_gang_env({GANG_ENV_ID: "g", GANG_ENV_SIZE: "x",
                          GANG_ENV_RANK: "0"}) is None
    assert read_gang_env({GANG_ENV_ID: "g", GANG_ENV_SIZE: "1",
                          GANG_ENV_RANK: "0"}) is None
    assert read_gang_env({GANG_ENV_ID: "g", GANG_ENV_SIZE: "2",
                          GANG_ENV_RANK: "2"}) is None


def test_token_digest_canonical():
    assert token_digest([1, 2, 3]) == token_digest((1, 2, 3))
    assert token_digest([1, 2, 3]) != token_digest([3, 2, 1])
    assert token_digest([]) == token_digest(None)
    assert len(token_digest([7])) == 16
    # numpy-ish int types digest identically to python ints
    class FakeInt(int):
        pass
    assert token_digest([FakeInt(5)]) == token_digest([5])


# -- leader/member protocol over real sockets --------------------------------


def _registry_with_leader_beat(leader, token=""):
    """A live registry whose table carries the leader's gang beat —
    what a booting member's ``gang_lookup`` poll resolves against."""
    reg = ReplicaRegistry(token=token).start()
    reg.observe({"op": "heartbeat", "addr": "127.0.0.1:9", "capacity": 4,
                 "outstanding": 0, "gen": leader.generation,
                 "gang": leader.gang_info()})
    return reg


def test_gang_forms_dispatches_and_verifies_digests():
    broken = []
    leader = GangLeader("replica/g1", size=3, generation=0,
                        on_break=broken.append).start()
    reg = _registry_with_leader_beat(leader)
    stop = threading.Event()
    members = [GangMember("replica/g1", 3, rank, 0, reg.addr,
                          execute=lambda head: [1, 2, head["n"]],
                          poll_interval=0.05, lookup_timeout=10.0)
               for rank in (1, 2)]
    threads = [threading.Thread(target=m.run, args=(stop,), daemon=True)
               for m in members]
    try:
        for t in threads:
            t.start()
        assert leader.wait_formed(timeout=10.0)
        assert leader.live == 3
        assert leader.gang_info()["live"] == 3

        # One dispatched request: both members mirror-execute and ack
        # the same digest the leader derives locally — no divergence.
        leader.dispatch({"op": "generate", "id": 7, "n": 3})
        deadline = time.monotonic() + 5.0
        while (members[0].served < 1 or members[1].served < 1) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        leader.observe_local(7, [1, 2, 3])
        time.sleep(0.1)
        assert leader.divergence == 0

        # A mismatched completion IS counted — the in-flight SPMD
        # token-identity check (acks already in, local arrives last).
        leader.dispatch({"op": "generate", "id": 8, "n": 4})
        deadline = time.monotonic() + 5.0
        while (members[0].served < 2 or members[1].served < 2) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        leader.observe_local(8, [9, 9, 9])
        deadline = time.monotonic() + 5.0
        while leader.divergence < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert leader.divergence == 2   # one per member
        assert not leader.broken
    finally:
        stop.set()
        leader.stop()
        reg.stop()
        for t in threads:
            t.join(timeout=5.0)


def test_join_fencing_rejects_wrong_gang_and_generation():
    leader = GangLeader("replica/g2", size=2, generation=3).start()
    try:
        for bad in ({"gang_id": "replica/g2", "rank": 1, "gen": 2},
                    {"gang_id": "replica/OTHER", "rank": 1, "gen": 3},
                    {"gang_id": "replica/g2", "rank": 0, "gen": 3},
                    {"gang_id": "replica/g2", "rank": 5, "gen": 3}):
            sock = wire.connect(leader.coord_addr, timeout=5.0)
            try:
                msg = dict(bad)
                msg["op"] = "gang_join"
                wire.send_msg(sock, msg, "")
                reply = wire.recv_msg(sock, "")
                assert reply["op"] == "gang_joined"
                assert reply["ok"] is False, bad
            finally:
                sock.close()
        assert not leader.formed
        assert leader.live == 1
    finally:
        leader.stop()


def test_member_zombie_fence_on_newer_generation_leader():
    """A member whose gang_lookup resolves to a NEWER generation is the
    zombie of a torn-down gang: it must give up, never join."""
    leader = GangLeader("replica/g3", size=2, generation=5).start()
    reg = _registry_with_leader_beat(leader)
    try:
        member = GangMember("replica/g3", 2, 1, generation=4,
                            registry_addr=reg.addr,
                            poll_interval=0.05, lookup_timeout=2.0)
        assert member.run() == "no_leader"
        assert not leader.formed
    finally:
        leader.stop()
        reg.stop()


def test_member_eof_breaks_gang_once():
    broken = []
    leader = GangLeader("replica/g4", size=3, generation=0,
                        on_break=broken.append).start()
    reg = _registry_with_leader_beat(leader)
    stop = threading.Event()
    outcomes = {}

    def run(rank, member_stop):
        m = GangMember("replica/g4", 3, rank, 0, reg.addr,
                       poll_interval=0.05, lookup_timeout=10.0)
        outcomes[rank] = m.run(member_stop)

    stop1 = threading.Event()
    t1 = threading.Thread(target=run, args=(1, stop1), daemon=True)
    t2 = threading.Thread(target=run, args=(2, stop), daemon=True)
    try:
        t1.start()
        t2.start()
        assert leader.wait_formed(timeout=10.0)
        # Sever rank 1: its socket closes, the leader flags the gang
        # broken and fires on_break exactly once.
        stop1.set()
        leader.dispatch({"op": "generate", "id": 1})   # wakes the loop
        t1.join(timeout=5.0)
        deadline = time.monotonic() + 5.0
        while not leader.broken and time.monotonic() < deadline:
            time.sleep(0.01)
        assert leader.broken
        assert broken == [1]
    finally:
        stop.set()
        leader.stop()
        reg.stop()
        t1.join(timeout=5.0)
        t2.join(timeout=5.0)
    # The surviving member sees the leader's teardown as EOF — member
    # death semantics are symmetric.
    assert outcomes.get(2) in ("leader_eof", "stopped")


def test_leader_stop_does_not_fire_on_break():
    broken = []
    leader = GangLeader("replica/g5", size=2, generation=0,
                        on_break=broken.append).start()
    reg = _registry_with_leader_beat(leader)
    stop = threading.Event()
    m = GangMember("replica/g5", 2, 1, 0, reg.addr,
                   poll_interval=0.05, lookup_timeout=10.0)
    t = threading.Thread(target=m.run, args=(stop,), daemon=True)
    try:
        t.start()
        assert leader.wait_formed(timeout=10.0)
    finally:
        leader.stop()      # deliberate teardown: no break callback
        time.sleep(0.2)
        stop.set()
        reg.stop()
        t.join(timeout=5.0)
    assert broken == []


def test_leader_handler_fans_out_and_observes_completions():
    """The replica-side wrap: plain generate heads dispatch to members
    before the leader serves them; completion tokens feed the digest
    check; control ops pass through untouched."""
    dispatched = []
    observed = []

    class StubLeader:
        def dispatch(self, head):
            dispatched.append(head)

        def observe_local(self, mid, tokens):
            observed.append((mid, list(tokens)))

    inner_calls = []

    def inner(msg, reply):
        inner_calls.append(msg)
        if isinstance(msg, dict) and msg.get("op") == "generate":
            reply({"op": "completion", "id": msg["id"],
                   "tokens": [4, 5]})
        else:
            reply({"op": "ok"})

    out = []
    handler = leader_handler(inner, StubLeader())
    handler({"op": "generate", "id": 42}, out.append)
    assert dispatched == [{"op": "generate", "id": 42}]
    assert observed == [(42, [4, 5])]
    assert out[-1]["op"] == "completion"
    handler({"op": "status"}, out.append)
    assert dispatched == [{"op": "generate", "id": 42}]   # no fan-out
    assert out[-1] == {"op": "ok"}


# -- registry: gang beats, lookup, gauges ------------------------------------


def _beat(reg, addr, **extra):
    msg = {"op": "heartbeat", "addr": addr, "capacity": 4,
           "outstanding": 0}
    msg.update(extra)
    reg.observe(msg)


def test_registry_gang_field_lookup_and_summary():
    clock = [0.0]
    reg = ReplicaRegistry(clock=lambda: clock[0])
    _beat(reg, "a:1", gen=2, gang={"id": "replica/g1", "size": 4,
                                   "live": 4, "coord": "c:1"})
    _beat(reg, "b:1", gen=2, gang={"id": "replica/g2", "size": 4,
                                   "live": 3, "coord": "c:2"})
    _beat(reg, "d:1")                       # single-process replica
    look = reg.gang_lookup("replica/g1")
    assert look["found"] and look["coord"] == "c:1"
    assert look["gen"] == 2 and look["size"] == 4
    assert not reg.gang_lookup("replica/absent")["found"]
    assert not reg.gang_lookup(None)["found"]

    agg = reg.gang_summary()
    assert agg == {"gangs": 2, "members": 8, "live": 7, "warming": 0,
                   "degraded": 1}
    roles = reg.role_summary()["unified"]
    assert roles["gangs"] == 2
    assert roles["gang_members"] == 8 and roles["gang_live"] == 7

    # Malformed sub-fields cost the FIELD, never the beat — and live
    # is clamped to size.
    _beat(reg, "a:1", gang={"id": 3, "size": "x", "live": 99,
                            "coord": ["no"]})
    assert len(reg.alive()) == 3
    rep = {r.addr: r for r in reg.alive()}["a:1"]
    assert rep.gang_id == "replica/g1" and rep.gang_size == 4
    assert rep.gang_live == 4 and rep.gang_coord == "c:1"
    _beat(reg, "a:1", gang="nope")          # not even a dict
    assert len(reg.alive()) == 3

    # A dead gang is debris awaiting eviction, not a serving gang the
    # gauge should count.
    clock[0] += 5.0
    _beat(reg, "b:1", gen=2, gang={"id": "replica/g2", "size": 4,
                                   "live": 3, "coord": "c:2"})
    reg.sweep()
    assert reg.gang_summary()["gangs"] == 1


def test_registry_gang_lookup_over_the_wire():
    reg = ReplicaRegistry().start()
    try:
        _beat(reg, "a:1", gen=0, gang={"id": "replica/g9", "size": 2,
                                       "live": 2, "coord": "c:9"})
        sock = wire.connect(reg.addr, timeout=5.0)
        try:
            wire.send_msg(sock, {"op": "gang_lookup",
                                 "gang_id": "replica/g9"}, "")
            reply = wire.recv_msg(sock, "")
        finally:
            sock.close()
        assert reply["found"] and reply["coord"] == "c:9"
    finally:
        reg.stop()


# -- scheduler: atomic placement + env contract ------------------------------


def _dyn_scheduler():
    from tfmesos_tpu.scheduler import TPUMesosScheduler

    class NullBackend:
        def start(self, s):
            pass

        def stop(self):
            pass

        def kill(self, task_id):
            pass

        def revive(self):
            pass

        def unplaceable(self, task):
            return None

    sched = TPUMesosScheduler.__new__(TPUMesosScheduler)
    # The minimum state add_gang/_batch_order/remove_task touch — the
    # full constructor wants a live backend + wire server.
    sched.dynamic = True
    sched._stopped = False
    sched.tasks = []
    sched.volumes = []
    sched.generation = 0
    sched._gang_seq = 0
    sched._dyn_index = {}
    sched._lock = threading.RLock()
    sched._fatal = None
    sched.backend = NullBackend()
    sched.on_dynamic_death = None
    from tfmesos_tpu.utils.logging import get_logger
    sched.log = get_logger("tfmesos_tpu.scheduler")
    sched._revive_backend = lambda why: None
    return sched


def test_add_gang_stamps_env_and_labels_atomically():
    sched = _dyn_scheduler()
    members = sched.add_gang("replica", ["cmd"] * 3, cpus=1.0,
                             mem=64.0, envs=[{"K": str(i)}
                                             for i in range(3)])
    assert len(members) == 3
    gid = members[0].gang
    assert gid == "replica/g1"
    for rank, t in enumerate(members):
        assert t.gang == gid and t.dynamic
        assert t.extra_env[GANG_ENV_ID] == gid
        assert t.extra_env[GANG_ENV_SIZE] == "3"
        assert t.extra_env[GANG_ENV_RANK] == str(rank)
        assert t.extra_env["K"] == str(rank)    # caller env preserved
        assert t.generation == members[0].generation
    # Fresh id per gang — the re-form fence's first half.
    again = sched.add_gang("replica", ["cmd"] * 2)
    assert again[0].gang == "replica/g2"
    with pytest.raises(ValueError):
        sched.add_gang("replica", [])
    with pytest.raises(ValueError):
        sched.add_gang("replica", ["a", "b"], envs=[{}])


def test_batch_order_places_gangs_all_or_nothing():
    from tfmesos_tpu.spec import Offer

    sched = _dyn_scheduler()
    gang = sched.add_gang("replica", ["cmd"] * 2, cpus=2.0, mem=100.0)
    loose = sched._add_task_locked("replica", "cmd", 1.0, 50.0, 0, None)

    # One 2-cpu offer: the gang cannot wholly fit — withheld, the loose
    # task still places.
    small = [Offer(id="o1", agent_id="a", hostname="h1",
                   cpus=2.0, mem=500.0, chips=0)]
    order = sched._batch_order(small)
    assert gang[0] not in order and gang[1] not in order
    assert loose in order

    # A batch with capacity for both members (split across hosts is
    # fine): the gang admits and sorts FIRST so loose tasks cannot eat
    # the reserved capacity.
    batch = [Offer(id="o2", agent_id="a", hostname="h1",
                   cpus=2.0, mem=500.0, chips=0),
             Offer(id="o3", agent_id="b", hostname="h2",
                   cpus=3.0, mem=500.0, chips=0)]
    order = sched._batch_order(batch)
    assert order[:2] == gang
    assert order[-1] is loose


def test_batch_order_admits_second_gang_only_if_it_also_fits():
    from tfmesos_tpu.spec import Offer

    sched = _dyn_scheduler()
    g1 = sched.add_gang("replica", ["cmd"] * 2, cpus=2.0, mem=100.0)
    g2 = sched.add_gang("replica", ["cmd"] * 2, cpus=2.0, mem=100.0)
    batch = [Offer(id="o1", agent_id="a", hostname="h1",
                   cpus=5.0, mem=500.0, chips=0)]
    order = sched._batch_order(batch)
    # 5 cpus hold one whole gang (4 cpus) but not two: exactly one
    # admitted, the other withheld for a bigger batch.
    assert len(order) == 2
    assert {t.gang for t in order} in ({g1[0].gang}, {g2[0].gang})


def test_dynamic_death_hook_fires_off_the_status_thread():
    sched = _dyn_scheduler()
    seen = []
    fired = threading.Event()

    def hook(task):
        seen.append((task, threading.current_thread().name))
        fired.set()

    sched.on_dynamic_death = hook
    task = sched.add_gang("replica", ["cmd"] * 2)[0]
    sched._fire_dynamic_death(sched.on_dynamic_death, task)
    assert fired.wait(5.0)
    assert seen[0][0] is task
    # The real dispatch path (on_status) spawns a named daemon thread;
    # assert the contract the launcher relies on: the hook never runs
    # under the scheduler lock (teardown kills siblings over HTTP).
    thread = threading.Thread(target=sched._fire_dynamic_death,
                              args=(sched.on_dynamic_death, task),
                              name="tpumesos-dyn-death", daemon=True)
    thread.start()
    thread.join(5.0)
    assert len(seen) == 2


# -- launcher: the gang manager (no processes) -------------------------------


class _StubGangSched:
    def __init__(self):
        self.removed = []
        self.generation = 0
        self._seq = 0
        self._idx = 0
        self.tasks = []

    def add_gang(self, job, cmds, cpus=1.0, mem=1024.0, chips=0):
        import types

        self._seq += 1
        gid = f"{job}/g{self._seq}"
        members = []
        for _ in cmds:
            members.append(types.SimpleNamespace(
                id=f"t{self._idx}", job_name=job, task_index=self._idx,
                gang=gid))
            self._idx += 1
        self.tasks.extend(members)
        return members

    def remove_task(self, tid):
        found = any(t.id == tid for t in self.tasks)
        self.tasks = [t for t in self.tasks if t.id != tid]
        self.removed.append(tid)
        return found

    def tasks_of(self, job):
        return [t for t in self.tasks if t.job_name == job]

    def bump_generation(self):
        self.generation += 1
        return self.generation


def _gang_fleet(**kw):
    from tfmesos_tpu.fleet.launcher import FleetServer

    fleet = FleetServer(replicas=2, gang_size=2, **kw)
    fleet.scheduler = _StubGangSched()
    return fleet


def test_launcher_gang_size_validation_and_sizing():
    from tfmesos_tpu.fleet.launcher import FleetServer

    with pytest.raises(ValueError):
        FleetServer(gang_size=0)
    # Gangs serve the unified tier; the disaggregated tiers keep their
    # one-process replicas.
    with pytest.raises(ValueError):
        FleetServer(gang_size=2, replicas=0, prefill_replicas=1,
                    decode_replicas=1)
    fleet = _gang_fleet()
    assert fleet.gang_size_for("unified") == 2
    assert fleet.gang_size_for("prefill") == 1
    assert fleet.gang_size_for("decode") == 1


def test_launcher_launch_kill_and_tier_actual_count_gangs_as_one():
    fleet = _gang_fleet()
    fleet._replica_cmd = lambda role, wv=None, model=None: "cmd"
    node = fleet.launch_gang("unified", "v1")
    assert node == "replica:0"              # rank 0 leads and routes
    with fleet._gang_lock:
        (gid, info), = fleet._gangs.items()
    assert info["leader_node"] == node and info["size"] == 2
    assert fleet._node_keys[node] == "unified"
    # Two member tasks, ONE replica.
    assert fleet.tier_actual("unified") == 1
    fleet.launch_gang("unified", "v1")
    assert fleet.tier_actual("unified") == 2

    # Killing the leader node kills the WHOLE gang — members without a
    # leader are debris, not a smaller replica.
    assert fleet.kill_replica(node)
    assert set(fleet.scheduler.removed) == set(info["task_ids"])
    with fleet._gang_lock:
        assert gid not in fleet._gangs
    assert node not in fleet._node_keys
    assert fleet.tier_actual("unified") == 1


def test_launcher_gang_death_reforms_once_with_fresh_id():
    from tfmesos_tpu.fleet.metrics import FleetMetrics

    fleet = _gang_fleet()
    fleet._replica_cmd = lambda role, wv=None, model=None: "cmd"
    fleet.metrics = FleetMetrics()
    fleet._started = True
    node = fleet.launch_gang("unified", "v1")
    with fleet._gang_lock:
        (gid, info), = fleet._gangs.items()
    members = [t for t in fleet.scheduler.tasks_of("replica")
               if t.gang == gid]

    # First member death: siblings torn down, generation bumped, the
    # gang re-forms under a FRESH id (the zombie fence's first half).
    fleet._on_dynamic_death(members[1])
    assert fleet.scheduler.generation == 1
    assert members[0].id in fleet.scheduler.removed
    assert members[1].id not in fleet.scheduler.removed  # already dead
    with fleet._gang_lock:
        (new_gid, new_info), = fleet._gangs.items()
    assert new_gid != gid
    assert new_info["key"] == "unified"
    assert new_info["weights_version"] == "v1"
    assert fleet.metrics.get("gang_reforms") == 1
    assert node not in fleet._node_keys
    assert fleet._node_keys[new_info["leader_node"]] == "unified"

    # The sibling's own death reports after the pop: a no-op, never a
    # second re-form.
    fleet._on_dynamic_death(members[0])
    assert fleet.metrics.get("gang_reforms") == 1
    with fleet._gang_lock:
        assert set(fleet._gangs) == {new_gid}
    # A gang-less task's death is not the gang path's business.
    import types

    fleet._on_dynamic_death(types.SimpleNamespace(id="x", gang=None))
    assert fleet.metrics.get("gang_reforms") == 1


# -- rid seeding (the PR 4 cross-exporter caveat, closed) --------------------


def test_rid_seed_for_node_disjoint_blocks():
    from tfmesos_tpu.fleet.replica import rid_seed_for_node

    seeds = {node: rid_seed_for_node(node)
             for node in ("replica:0", "replica:1", "replica:2",
                          "prefill:0", "decode:0", "m.x:replica:7")}
    # Distinct nodes get distinct 1024-rid blocks; every seed stays
    # int32-safe with increment headroom.
    assert len(set(seeds.values())) == len(seeds)
    for seed in seeds.values():
        assert seed % 1024 == 0
        assert 0 <= seed < 2 ** 30
    assert rid_seed_for_node("") == 0       # direct/test replica
    assert rid_seed_for_node("replica:0") == rid_seed_for_node("replica:0")
