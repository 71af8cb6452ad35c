"""Virtual-cluster stress harness: hundreds of simulated nodes per
process against a REAL head over the REAL RPC stack.

Reference analogue: the reference's in-process multi-node simulation
(cluster_utils.Cluster / ray_start_cluster) scaled past what real OS
processes allow — a worker subprocess per node tops out around a
dozen on CI hardware; control-plane scale bugs (lock convoys, O(n²)
view fan-out, journal stalls) only appear in the hundreds.

What is real: the head runs as its own subprocess (so
``chaos.kill_head()`` is a true kill -9), every byte crosses the
framed-socket RPC layer, leases/epochs/journal behave exactly as in
production.  What is simulated: node HEARTBEAT STATE — each virtual
node is a lease-holding record whose beats multiplex through the
``heartbeat_batch`` RPC over a small connection pool instead of one
socket per node.  Chaos composes per NODE: the pump runs each virtual
node's beat through ``chaos.on_rpc("heartbeat", tag=node_id)`` before
batching it, so ``chaos.partition_node(substr, dur)`` and
``chaos.drop_heartbeats(frac)`` hit exactly the nodes a real
per-node client would lose.

The soak protocol (test_vcluster.py):

    vc = VCluster(n_nodes=300, lease_ttl_s=2.0, hb_interval_s=0.5)
    vc.start()
    vc.load(duration_s=6.0, threads=8)      # background mixed ops
    chaos.kill_head()                        # mid-load kill -9
    vc.restart_head()                        # same port, same storage
    vc.wait_converged()
    report = vc.verify()                     # zero lost acked mutations

Every mutation the harness ACKS is remembered in a ledger; ``verify``
replays the ledger against the recovered head — a lost acked write or
an accepted stale-epoch write is a hard failure.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.cluster.rpc import (TRANSPORT_ERRORS, ReconnectingClient)
from ray_tpu.exceptions import StaleEpochError
from ray_tpu.experimental import chaos


class VirtualNode:
    __slots__ = ("node_id", "name", "resources", "epoch", "lease_id",
                 "available", "sent_avail", "reregistrations")

    def __init__(self, idx: int, cpus: float):
        self.node_id = f"vnode-{idx:04d}-{uuid.uuid4().hex[:8]}"
        self.name = f"v{idx}"
        self.resources = {"CPU": cpus, f"v{idx}": 1.0}
        self.epoch: Optional[int] = None
        self.lease_id = ""
        self.available = dict(self.resources)
        self.sent_avail: Optional[Dict[str, float]] = None
        self.reregistrations = 0


class VCluster:
    """``n_nodes`` virtual nodes multiplexed over ``n_conns`` real RPC
    connections, with a subprocess head (unless ``head_address`` points
    at an existing one).  Timing knobs compress time for CI: the head
    subprocess inherits ``lease_ttl_s`` via RAY_TPU_LEASE_TTL_S and
    compaction knobs via the RAY_TPU_HEAD_* environment."""

    def __init__(self, n_nodes: int = 25, *, cpus_per_node: float = 4.0,
                 head_address: Optional[str] = None,
                 storage: Optional[str] = None,
                 hb_interval_s: float = 0.5,
                 lease_ttl_s: float = 3.0,
                 n_conns: int = 8, seed: int = 0,
                 head_env: Optional[Dict[str, str]] = None):
        self.n_nodes = int(n_nodes)
        self.nodes = [VirtualNode(i, cpus_per_node)
                      for i in range(self.n_nodes)]
        self.hb_interval_s = float(hb_interval_s)
        self.lease_ttl_s = float(lease_ttl_s)
        self.storage = storage
        self._head_env = dict(head_env or {})
        self._external_head = head_address
        self.head_address = head_address or ""
        self._head_port = 0
        self._proc: Optional[subprocess.Popen] = None
        # Hot-standby pair (start_standby): its own subprocess +
        # storage, tailing the primary's journal.
        self.standby_address = ""
        self.standby_storage: Optional[str] = None
        self._standby_proc: Optional[subprocess.Popen] = None
        self.primary_ttl_s = max(0.5, float(lease_ttl_s) / 2)
        self.kill_times: List[float] = []
        # One cooldown map for EVERY client this harness makes (pump
        # conns, drivers, load workers): the first client to probe a
        # dead head spares the rest — without it the single pump
        # thread pays n_conns serial dial budgets after a failover
        # and renewals can outlast the node lease.
        self._cooldowns: Dict[str, tuple] = {}
        self._n_conns = max(1, min(int(n_conns), self.n_nodes))
        self._conns: List[ReconnectingClient] = []
        self._rng = random.Random(seed)
        self._stop = threading.Event()
        self._pump: Optional[threading.Thread] = None
        self._view_seq = None
        self._lock = threading.Lock()
        # The acked-mutation ledger verify() replays: [("kv", key,
        # value) | ("actor", actor_id, node_id)].
        self.acked: List[Tuple] = []
        # Nodes whose lease was revoked at least once (they had to
        # re-register): the head legitimately DROPPED their actors at
        # death time, so verify() must not count those as lost.
        self.fenced_nodes: set = set()
        # Ops timeline for goodput analysis: (monotonic_ts, ok_bool).
        self.op_events: List[Tuple[float, bool]] = []
        self.placement_latencies: List[float] = []
        self.stale_epoch_accepted = 0  # must stay 0 (verify checks)
        self._load_threads: List[threading.Thread] = []
        self._load_stop = threading.Event()

    # ------------------------------------------------------------- head
    def _spawn_head(self) -> None:
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["RAY_TPU_LEASE_TTL_S"] = str(self.lease_ttl_s)
        # The PRIMARY's value is authoritative (the standby adopts it
        # from the attach reply) — it must be exported here, not just
        # on the standby spawn, or the promotion window silently
        # defaults to the full node lease TTL.
        env["RAY_TPU_HEAD_PRIMARY_TTL_S"] = str(self.primary_ttl_s)
        env.setdefault("RAY_TPU_HEAD_COMPACT_EVERY_S", "2.0")
        env.update(self._head_env)
        cmd = [sys.executable, "-m", "ray_tpu.cluster.head",
               "--port", str(self._head_port)]
        if self.storage:
            cmd += ["--storage", self.storage]
        self._proc = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 30.0
        line = ""
        while time.monotonic() < deadline:
            line = (self._proc.stdout.readline() or b"").decode(
                errors="replace").strip()
            if line.startswith("RAY_TPU_HEAD_ADDRESS="):
                break
            if self._proc.poll() is not None:
                raise RuntimeError(
                    f"head subprocess died at start: {line}")
        else:
            raise TimeoutError("head subprocess never printed its "
                               "address")
        self.head_address = line.split("=", 1)[1]
        self._head_port = int(self.head_address.rsplit(":", 1)[1])
        chaos.register_head_process(self._proc)

    def restart_head(self) -> None:
        """Respawn the head at the SAME port with the same storage —
        the recovery half of a kill -9 (clients re-dial the address
        they already hold; state replays from snapshot + journal)."""
        if self._external_head:
            raise RuntimeError("vcluster does not own this head")
        if self._proc is not None and self._proc.poll() is None:
            self._proc.kill()
            self._proc.wait(timeout=10.0)
        self._spawn_head()

    def kill_head(self):
        """kill -9 the head mid-flight (delegates to chaos so tests
        read as chaos scripts)."""
        self.kill_times.append(time.monotonic())
        return chaos.kill_head()

    def head_alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    # --------------------------------------------------- hot standby
    def _candidates(self) -> List[str]:
        return [a for a in (self.head_address, self.standby_address)
                if a]

    def start_standby(self, storage: Optional[str] = None,
                      sync_timeout_s: float = 120.0,
                      repl_mode: Optional[str] = None) -> str:
        """Spawn a hot-standby head subprocess tailing the primary's
        journal; blocks until it reports seeded + caught up.  Returns
        its address.  Timing: the standby promotes itself when the
        primary ships nothing for ``primary_ttl_s`` (half the node
        lease TTL by default — failover inside one node lease)."""
        if self._standby_proc is not None and \
                self._standby_proc.poll() is None:
            raise RuntimeError("standby already running")
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["RAY_TPU_LEASE_TTL_S"] = str(self.lease_ttl_s)
        env["RAY_TPU_HEAD_PRIMARY_TTL_S"] = str(self.primary_ttl_s)
        env.setdefault("RAY_TPU_HEAD_COMPACT_EVERY_S", "2.0")
        env.update(self._head_env)
        if repl_mode:
            env["RAY_TPU_HEAD_REPL_MODE"] = repl_mode
        self.standby_storage = storage or (
            self.storage + ".standby" if self.storage else None)
        cmd = [sys.executable, "-m", "ray_tpu.cluster.head",
               "--port", "0", "--standby-of", self.head_address]
        if self.standby_storage:
            cmd += ["--storage", self.standby_storage]
        self._standby_proc = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT)
        import select

        deadline = time.monotonic() + sync_timeout_s
        line = ""
        while time.monotonic() < deadline:
            # select before readline: a standby wedged mid-seed
            # (unreachable primary) stays ALIVE and SILENT — a bare
            # blocking readline would hang past the deadline forever.
            ready, _w, _x = select.select(
                [self._standby_proc.stdout], [], [],
                max(0.1, min(1.0, deadline - time.monotonic())))
            if not ready:
                if self._standby_proc.poll() is not None:
                    raise RuntimeError(
                        f"standby subprocess died at start: {line}")
                continue
            line = (self._standby_proc.stdout.readline()
                    or b"").decode(errors="replace").strip()
            if line.startswith("RAY_TPU_HEAD_ADDRESS="):
                break
            if self._standby_proc.poll() is not None:
                raise RuntimeError(
                    f"standby subprocess died at start: {line}")
        else:
            raise TimeoutError(
                "standby subprocess never printed its address")
        self.standby_address = line.split("=", 1)[1]
        # Existing connections learn the widened head set; clients
        # created later pick it up from _candidates().
        for c in self._conns:
            c.set_candidates(self._candidates())
        # The primary also must see it attached + caught up before
        # chaos starts (sync mode: acks already wait on it).
        conn = ReconnectingClient(self.standby_address)
        try:
            while time.monotonic() < deadline:
                try:
                    st = conn.call("repl_status", {}, timeout=5.0)
                except TRANSPORT_ERRORS:
                    time.sleep(0.2)
                    continue
                if st.get("synced"):
                    return self.standby_address
                time.sleep(0.1)
        finally:
            conn.close()
        raise TimeoutError("standby never reported synced")

    def standby_alive(self) -> bool:
        return (self._standby_proc is not None
                and self._standby_proc.poll() is None)

    def kill_standby(self):
        """kill -9 the standby (sync-mode primaries stall typed until
        a standby re-attaches or is detached)."""
        if self._standby_proc is None or \
                self._standby_proc.poll() is not None:
            raise RuntimeError("no live standby to kill")
        import signal as _signal

        self._standby_proc.send_signal(_signal.SIGKILL)
        self._standby_proc.wait(timeout=10.0)
        return self._standby_proc

    def promote(self, timeout_s: float = 30.0) -> Dict[str, Any]:
        """Promote the standby NOW (tests that don't want to wait out
        the primary lease)."""
        conn = ReconnectingClient(self.standby_address)
        try:
            return conn.call_retry("promote",
                                   {"reason": "vcluster"},
                                   timeout=10.0,
                                   deadline_s=timeout_s)
        finally:
            conn.close()

    def partition_heads(self, duration_s: float) -> None:
        """Sever the replication link for ``duration_s``: the standby
        sees a silent primary (lease lapses → it promotes) while the
        primary keeps running — the split-brain scenario the
        generation fencing must win."""
        conn = ReconnectingClient(self.head_address)
        try:
            conn.call("repl_control",
                      {"partition_s": float(duration_s)},
                      timeout=10.0)
        finally:
            conn.close()

    def repl_status(self, standby: bool = False) -> Dict[str, Any]:
        conn = ReconnectingClient(self.standby_address if standby
                                  else self.head_address)
        try:
            return conn.call_retry("repl_status", {}, timeout=10.0,
                                   deadline_s=30.0)
        finally:
            conn.close()

    def wait_promoted(self, timeout_s: float = 30.0) -> None:
        """Block until the standby reports role=primary."""
        deadline = time.monotonic() + timeout_s
        conn = ReconnectingClient(self.standby_address)
        try:
            while time.monotonic() < deadline:
                try:
                    st = conn.call("repl_status", {}, timeout=5.0)
                    if st.get("role") == "primary":
                        return
                except TRANSPORT_ERRORS:
                    pass
                time.sleep(0.1)
        finally:
            conn.close()
        raise TimeoutError("standby never promoted")

    # ------------------------------------------------------------ start
    def start(self, register_timeout_s: float = 120.0) -> None:
        if not self.head_address:
            self._spawn_head()
        self._conns = [ReconnectingClient(
            self.head_address, candidates=self._candidates(),
            shared_cooldowns=self._cooldowns)
            for _ in range(self._n_conns)]
        # Parallel registration: at 300 nodes, serial round-trips with
        # per-mutation fsync dominate startup.
        groups = [self.nodes[i::self._n_conns]
                  for i in range(self._n_conns)]
        errs: List[BaseException] = []

        def reg(conn, group):
            try:
                for node in group:
                    self._register_node(conn, node,
                                        deadline_s=register_timeout_s)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=reg, args=(c, g),
                                    daemon=True)
                   for c, g in zip(self._conns, groups)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=register_timeout_s)
        if errs:
            raise errs[0]
        self._pump = threading.Thread(target=self._pump_loop,
                                      daemon=True, name="vcluster-pump")
        self._pump.start()

    def _register_node(self, conn, node: VirtualNode, *,
                       deadline_s: float = 30.0) -> None:
        resp = conn.call_idempotent("register_node", {
            "node_id": node.node_id, "address": f"vnode://{node.name}",
            "resources": dict(node.resources), "name": node.name,
            "labels": {"vcluster": "1"},
        }, deadline_s=deadline_s)
        node.epoch = resp.get("epoch")
        node.lease_id = resp.get("lease_id", "")
        node.sent_avail = None

    # ------------------------------------------------------------- pump
    def _pump_loop(self) -> None:
        """One thread beats for EVERY virtual node: per-node chaos
        hooks, then one heartbeat_batch per connection per interval."""
        groups = [self.nodes[i::self._n_conns]
                  for i in range(self._n_conns)]
        while not self._stop.wait(self.hb_interval_s):
            for conn, group in zip(self._conns, groups):
                beats, beat_nodes = [], []
                for node in group:
                    if node.epoch is None:
                        continue  # registration still in flight
                    try:
                        # Per-node chaos: a partitioned/dropped node's
                        # beat never reaches the wire, exactly as if
                        # it held its own client.
                        chaos.on_rpc("heartbeat", node.node_id)
                    except ConnectionError:
                        continue
                    beat: Dict[str, Any] = {"node_id": node.node_id,
                                            "epoch": node.epoch}
                    if node.available != node.sent_avail:
                        beat["available"] = dict(node.available)
                    beats.append(beat)
                    beat_nodes.append(node)
                if not beats:
                    continue
                try:
                    resp = conn.call("heartbeat_batch", {
                        "beats": beats, "view_seq": self._view_seq,
                    }, timeout=10.0)
                except StaleEpochError:
                    # NotPrimary included: the beat reached a deposed
                    # primary mid-failover — walk the head set.
                    conn.failover()
                    continue
                except TRANSPORT_ERRORS:
                    continue  # head down/partitioned: next tick retries
                if resp.get("deposed"):
                    conn.failover()  # fenced ex-primary: walk the set
                    continue
                self._view_seq = resp.get("view_seq", self._view_seq)
                for node, beat, r in zip(beat_nodes, beats,
                                         resp.get("replies") or ()):
                    if r.get("reregister"):
                        with self._lock:
                            self.fenced_nodes.add(node.node_id)
                        try:
                            self._register_node(conn, node,
                                                deadline_s=10.0)
                            node.reregistrations += 1
                        except TRANSPORT_ERRORS:
                            pass  # next tick
                        continue
                    if "available" in beat and r.get("ok"):
                        node.sent_avail = beat["available"]
                    if r.get("need_available"):
                        node.sent_avail = None

    # -------------------------------------------------------- workload
    def _driver(self) -> ReconnectingClient:
        return ReconnectingClient(self.head_address,
                                  candidates=self._candidates(),
                                  shared_cooldowns=self._cooldowns)

    def load(self, duration_s: float, threads: int = 4,
             *, place_frac: float = 0.5, kv_frac: float = 0.25,
             actor_frac: float = 0.15,
             op_deadline_s: float = 15.0) -> None:
        """Sustained mixed workload (place / kv_put / register_actor /
        lookup) from ``threads`` driver threads.  Non-blocking: call
        ``join_load()`` (or ``stop()``) to wait it out.  Every acked
        mutation lands in the ledger; transport failures during a head
        outage retry under ``op_deadline_s`` and count against goodput
        until they succeed."""
        self._load_stop.clear()
        deadline = time.monotonic() + duration_s

        def worker(widx: int):
            rng = random.Random(1000 + widx)
            conn = self._driver()
            seq = 0
            try:
                while (time.monotonic() < deadline
                       and not self._load_stop.is_set()):
                    seq += 1
                    roll = rng.random()
                    ok = False
                    t0 = time.monotonic()
                    try:
                        if roll < place_frac:
                            r = conn.call_retry(
                                "place",
                                {"resources": {"CPU": 1.0}},
                                timeout=5.0,
                                deadline_s=op_deadline_s)
                            ok = bool(r.get("ok"))
                            if ok:
                                self.placement_latencies.append(
                                    time.monotonic() - t0)
                        elif roll < place_frac + kv_frac:
                            key = f"w{widx}-k{seq}"
                            val = {"w": widx, "seq": seq}
                            r = conn.call_idempotent(
                                "kv_put",
                                {"key": key, "value": val,
                                 "ns": "vcluster"},
                                timeout=5.0,
                                deadline_s=op_deadline_s)
                            ok = bool(r.get("ok"))
                            if ok:
                                with self._lock:
                                    self.acked.append(
                                        ("kv", key, val))
                        elif roll < place_frac + kv_frac + actor_frac:
                            aid = uuid.uuid4().bytes[:8]
                            node = rng.choice(self.nodes)
                            r = conn.call_idempotent(
                                "register_actor",
                                {"actor_id": aid,
                                 "node_id": node.node_id,
                                 "address": f"vnode://{node.name}",
                                 "name": "", "namespace": ""},
                                timeout=5.0,
                                deadline_s=op_deadline_s)
                            ok = bool(r.get("ok"))
                            if ok:
                                with self._lock:
                                    self.acked.append(
                                        ("actor", aid, node.node_id))
                        else:
                            conn.call_retry(
                                "kv_get",
                                {"key": f"w{widx}-k{rng.randint(1, max(1, seq))}",
                                 "ns": "vcluster"},
                                timeout=5.0,
                                deadline_s=op_deadline_s)
                            ok = True
                    except StaleEpochError:
                        # NotPrimaryError included (subclass): the op
                        # reached a standby/deposed head mid-failover
                        # — typed, never applied.  Walk the head set
                        # and count the op against goodput.
                        ok = False
                        conn.failover()
                    except TRANSPORT_ERRORS:
                        ok = False
                    with self._lock:
                        self.op_events.append((time.monotonic(), ok))
            finally:
                conn.close()

        self._load_threads = [
            threading.Thread(target=worker, args=(i,), daemon=True,
                             name=f"vcluster-load-{i}")
            for i in range(threads)]
        for t in self._load_threads:
            t.start()

    def join_load(self, timeout_s: float = 120.0) -> None:
        deadline = time.monotonic() + timeout_s
        for t in self._load_threads:
            t.join(timeout=max(0.1, deadline - time.monotonic()))
        self._load_threads = []

    # ------------------------------------------------------ verification
    def alive_nodes(self, conn: Optional[ReconnectingClient] = None
                    ) -> int:
        own = conn is None
        conn = conn or self._driver()
        try:
            nodes = conn.call_retry("list_nodes", {}, timeout=10.0,
                                    deadline_s=30.0)
            return sum(1 for n in nodes if n["alive"])
        finally:
            if own:
                conn.close()

    def wait_converged(self, timeout_s: float = 60.0,
                       target: Optional[int] = None) -> None:
        """Block until every virtual node holds a live lease again
        (post-restart reattach has quiesced)."""
        target = self.n_nodes if target is None else target
        conn = self._driver()
        try:
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                try:
                    if self.alive_nodes(conn) >= target:
                        return
                except TRANSPORT_ERRORS:
                    pass
                time.sleep(self.hb_interval_s)
            raise TimeoutError(
                f"vcluster did not reconverge to {target} live nodes "
                f"within {timeout_s}s (have {self.alive_nodes(conn)})")
        finally:
            conn.close()

    def verify(self) -> Dict[str, Any]:
        """Replay the acked-mutation ledger against the (recovered)
        head: every acked kv_put must read back its value, every acked
        actor registration must resolve.  Returns a report; callers
        assert ``report["missing"] == []``."""
        conn = self._driver()
        missing: List[Tuple] = []
        skipped_dead = 0
        try:
            with self._lock:
                ledger = list(self.acked)
                fenced = set(self.fenced_nodes)
            for entry in ledger:
                if entry[0] == "kv":
                    _kind, key, val = entry
                    r = conn.call_retry("kv_get",
                                        {"key": key, "ns": "vcluster"},
                                        timeout=10.0, deadline_s=30.0)
                    if not r.get("found") or r.get("value") != val:
                        missing.append(entry)
                else:
                    _kind, aid, nid = entry
                    r = conn.call_retry("lookup_actor",
                                        {"actor_id": aid},
                                        timeout=10.0, deadline_s=30.0)
                    if not r.get("found"):
                        if nid in fenced:
                            # The node's lease was revoked: the head
                            # DROPPED its actors at death time — a
                            # legitimate state transition the journal
                            # recorded, not a lost write.
                            skipped_dead += 1
                        else:
                            missing.append(entry)
        finally:
            conn.close()
        return {"checked": len(ledger), "missing": missing,
                "skipped_dead_node": skipped_dead,
                "stale_epoch_accepted": self.stale_epoch_accepted}

    def zombie_write_check(self, node: VirtualNode,
                           old_epoch: int) -> bool:
        """Attempt a write with a FENCED epoch; returns True when the
        head rejected it typed (the invariant the soak asserts).  An
        accepted write bumps ``stale_epoch_accepted``."""
        from ray_tpu.exceptions import StaleEpochError

        conn = self._driver()
        conn.chaos_tag = node.node_id
        try:
            conn.call("register_actor", {
                "actor_id": uuid.uuid4().bytes[:8],
                "node_id": node.node_id,
                "address": f"vnode://{node.name}",
                "name": "", "namespace": "",
                "epoch": old_epoch, "epoch_node": node.node_id,
            }, timeout=10.0)
        except StaleEpochError:
            return True
        except TRANSPORT_ERRORS:
            return True  # never landed — not an accepted stale write
        finally:
            conn.close()
        with self._lock:
            self.stale_epoch_accepted += 1
        return False

    # ------------------------------------------------------------- stats
    def goodput(self, bucket_s: float = 1.0
                ) -> List[Tuple[float, float]]:
        """(bucket_start_rel_s, ok_ops_per_s) series over the load
        window — the reconvergence curve the soak plots."""
        with self._lock:
            events = sorted(self.op_events)
        if not events:
            return []
        t0 = events[0][0]
        out: Dict[int, int] = {}
        for ts, ok in events:
            if ok:
                b = int((ts - t0) / bucket_s)
                out[b] = out.get(b, 0) + 1
        return [(b * bucket_s, n / bucket_s)
                for b, n in sorted(out.items())]

    def unavailability_ms(self,
                          after_ts: Optional[float] = None,
                          window_s: float = 30.0) -> Optional[float]:
        """Goodput outage around a head kill: the LARGEST gap between
        consecutive successful ops whose span intersects
        [``after_ts``, ``after_ts + window_s``] (default: the most
        recent ``kill_head``).  Max-gap, not first-op-after — an
        in-flight ack draining right after the kill timestamp must
        not mask the real dip.  None without enough signal."""
        if after_ts is None:
            after_ts = self.kill_times[-1] if self.kill_times else None
        if after_ts is None:
            return None
        with self._lock:
            oks = sorted(ts for ts, ok in self.op_events if ok)
        if len(oks) < 2:
            return None
        worst = 0.0
        for prev, cur in zip(oks, oks[1:]):
            if cur < after_ts or prev > after_ts + window_s:
                continue
            worst = max(worst, cur - prev)
        return round(worst * 1000.0, 1)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            lats = sorted(self.placement_latencies)
            n_ok = sum(1 for _t, ok in self.op_events if ok)
            n_all = len(self.op_events)

        def pct(p: float):
            return (round(lats[min(len(lats) - 1,
                                   int(p * len(lats)))] * 1000, 2)
                    if lats else None)

        return {
            "nodes": self.n_nodes,
            "ops_total": n_all, "ops_ok": n_ok,
            "acked_mutations": len(self.acked),
            "placement_p50_ms": pct(0.50),
            "placement_p99_ms": pct(0.99),
            "reregistrations": sum(n.reregistrations
                                   for n in self.nodes),
            "stale_epoch_accepted": self.stale_epoch_accepted,
        }

    # ---------------------------------------------------------- teardown
    def stop(self) -> None:
        self._load_stop.set()
        self.join_load(timeout_s=10.0)
        self._stop.set()
        if self._pump is not None:
            self._pump.join(timeout=5.0)
        for c in self._conns:
            c.close()
        self._conns = []
        for proc in (self._proc, self._standby_proc):
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    proc.kill()


def main() -> int:  # pragma: no cover - CLI soak driver
    import argparse
    import tempfile

    ap = argparse.ArgumentParser(
        description="virtual-cluster soak: N nodes, sustained load, "
                    "head kill -9 mid-load, verify zero lost acks")
    ap.add_argument("--nodes", type=int, default=300)
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--kill-at", type=float, default=None,
                    help="seconds into the load to kill -9 the head "
                         "(default: duration/3)")
    ap.add_argument("--lease-ttl", type=float, default=2.0)
    ap.add_argument("--hb-interval", type=float, default=0.5)
    args = ap.parse_args()

    storage = os.path.join(tempfile.mkdtemp(prefix="vcluster-"),
                           "head.bin")
    vc = VCluster(args.nodes, storage=storage,
                  lease_ttl_s=args.lease_ttl,
                  hb_interval_s=args.hb_interval)
    kill_at = (args.kill_at if args.kill_at is not None
               else args.duration / 3)
    try:
        t0 = time.monotonic()
        vc.start()
        print(f"# {args.nodes} nodes registered in "
              f"{time.monotonic() - t0:.1f}s", file=sys.stderr)
        vc.load(args.duration, threads=args.threads)
        time.sleep(kill_at)
        print("# kill -9 head", file=sys.stderr)
        vc.kill_head()
        time.sleep(min(2.0, args.duration / 10))
        vc.restart_head()
        vc.join_load(timeout_s=args.duration + 60)
        vc.wait_converged(timeout_s=60.0)
        report = vc.verify()
        out = {**vc.stats(), "missing_acked": len(report["missing"]),
               "goodput": vc.goodput()}
        print(json.dumps(out, indent=2))
        return 0 if not report["missing"] else 1
    finally:
        vc.stop()


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
