"""Cluster head: the control-plane authority.

Reference analogue: the GCS server (src/ray/gcs/gcs_server/gcs_server.h:88)
— node table (gcs_node_manager.h:45), actor registry + named actors
(gcs_actor_manager.h:308), placement groups
(gcs_placement_group_manager.h:228), internal KV (gcs_kv_manager.h),
health probing (gcs_health_check_manager.h:45).

Differences by design: scheduling here is *capacity-fit placement* — the
head picks a node whose total resources fit the demand (preferring the
most currently-available node from heartbeats) and the node's own local
scheduler gates actual execution.  This mirrors the reference's
two-level split (GCS/cluster policy picks, raylet local dispatch gates).

Liveness is **lease-fenced** (the classic fencing-token pattern):
registration mints a ``(lease_id, epoch)`` pair, heartbeats renew the
lease, and a node declared dead has its epoch fenced — a later
re-registration mints a strictly newer epoch, and any mutating RPC
still carrying the old one is rejected typed (``StaleEpochError``)
instead of silently overwriting live state.

Durability is **journaled** (journal.py): each mutating handler appends
redo records to a WAL and fsyncs ONCE before its reply ships; a
background compactor folds the log into a snapshot.  Restart recovery =
snapshot + journal-tail replay, idempotency cache included, so a
retried client mutation straddling a head kill -9 still dedups.

Resource sync is **delta-compressed**: nodes send availability only
when it changed, the head replies with per-entry view deltas against
the node's last acked ``view_seq`` (lease renewal piggybacks), and
``heartbeat_batch`` folds many virtual nodes' beats into one RPC
(tools/vcluster.py rides it).

Hot tables (actors, named actors, KV, PGs) live behind the sharded
store interface in tables.py — reads take one shard lock, not the
global mutation lock, and the interface is the unit a replicated head
would partition (ROADMAP item 5).
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from ..exceptions import StaleEpochError
from ..observability import alerts as alerts_mod
from ..observability import tsdb as tsdb_mod
from . import journal as journal_mod
from .replication import ReplicationSender, _repl_metrics
from .retention import DiskRing
from .rpc import (IDEMPOTENCY_KEY, ClientPool, IdempotencyCache,
                  RpcClient, RpcServer, _rpc_metrics)
from .serialization import loads
from .tables import ShardedTable

# Timing knobs, env-tunable (the vcluster harness compresses time by
# shrinking these; see docs/fault_tolerance.md).  Module values are the
# defaults — HeadServer re-reads the environment at construction so a
# test can set a knob after import.
_LEASE_TTL_S = 10.0     # lease duration == heartbeats missed before a
# node is declared dead (was _DEAD_AFTER_S)
_DEAD_AFTER_S = _LEASE_TTL_S  # legacy alias
_RESTART_TIMEOUT_S = 300.0
_RESTART_RETRY_S = 1.0  # restart-loop backoff between failed attempts
_COMPACT_EVERY_S = 30.0
_COMPACT_BYTES = 4 << 20


_RESERVATION_TTL_S = 2.5  # ≥ 2 heartbeats: by then the placed task is
# either reflected in the node's reported availability or it never ran


def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _lease_metrics():
    """Lease/fencing counters (rebuilt after registry resets)."""
    from ..observability import metrics as _metrics

    return _metrics.metric_group("head_lease", lambda: {
        "grants": _metrics.Counter(
            "ray_tpu_lease_grants_total",
            "leases minted at node (re)registration"),
        "renewals": _metrics.Counter(
            "ray_tpu_lease_renewals_total",
            "lease renewals piggybacked on heartbeats"),
        "expirations": _metrics.Counter(
            "ray_tpu_lease_expirations_total",
            "leases expired by the reaper (node declared dead)"),
        "stale_rejections": _metrics.Counter(
            "ray_tpu_lease_stale_epoch_rejections_total",
            "mutating RPCs rejected with StaleEpochError",
            tag_keys=("method",)),
        "stale_heartbeats": _metrics.Counter(
            "ray_tpu_lease_stale_heartbeats_total",
            "heartbeats from fenced epochs answered with reregister"),
    })


class NodeEntry:
    __slots__ = ("node_id", "address", "total", "available",
                 "last_heartbeat", "alive", "labels", "reserved", "name",
                 "lease_id", "epoch", "lease_expires", "view_seq",
                 "await_avail")

    def __init__(self, node_id: str, address: str,
                 total: Dict[str, float], labels: Dict[str, str],
                 name: str = "", lease_id: str = "", epoch: int = 0):
        self.node_id = node_id
        self.address = address
        self.name = name
        self.total = dict(total)
        self.available = dict(total)
        self.last_heartbeat = time.monotonic()
        self.alive = True
        self.labels = labels
        # Lease-fenced liveness: minted at registration, renewed by
        # heartbeats; a write carrying an epoch != this one is fenced.
        self.lease_id = lease_id
        self.epoch = epoch
        self.lease_expires = 0.0
        # Monotonic stamp of the last change to this entry's resource
        # view (availability/totals/liveness) — the delta-sync cursor.
        self.view_seq = 0
        # Set on journal replay: the head has registration-time totals
        # but no live availability; ask the node for a full report.
        self.await_avail = False
        # Placement debits not yet visible in a heartbeat:
        # [(expiry, demand)].  Heartbeats report ground truth but lag;
        # without this, two rapid placements both see the same
        # availability and oversubscribe a node.
        self.reserved: List[Tuple[float, Dict[str, float]]] = []

    def effective_available(self) -> Dict[str, float]:
        now = time.monotonic()
        self.reserved = [(t, d) for t, d in self.reserved if t > now]
        out = dict(self.available)
        for _t, demand in self.reserved:
            for k, v in demand.items():
                out[k] = out.get(k, 0.0) - v
        return out

    def reserve(self, demand: Dict[str, float]):
        self.reserved.append(
            (time.monotonic() + _RESERVATION_TTL_S, dict(demand)))


class HeadServer:
    """``storage_path`` enables GCS fault tolerance (reference:
    Redis-backed table storage, store_client/redis_store_client.h:106 +
    gcs_init_data.h replay): durable tables (KV, actor registry, named
    actors, PGs, node leases) journal to a WAL on mutation (snapshot +
    journal-tail replay on restart at the same address — see
    journal.py); nodes reattach through the heartbeat ``reregister``
    handshake.  ``persist_mode`` "journal" (default) appends one
    fsync'd redo record per mutation; "snapshot" keeps the seed's
    full-snapshot-per-mutation behavior (the bench's baseline)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 storage_path: Optional[str] = None,
                 lease_ttl_s: Optional[float] = None,
                 persist_mode: Optional[str] = None,
                 standby_of: Optional[str] = None,
                 repl_mode: Optional[str] = None,
                 primary_ttl_s: Optional[float] = None,
                 repl_timeout_s: Optional[float] = None):
        # RLock: the _mut wrapper holds it across {epoch fence +
        # handler} so a node cannot be declared dead (epoch fenced)
        # between the check and the table write — the handlers
        # re-acquire reentrantly.
        self._lock = threading.RLock()
        self._lease_ttl = (lease_ttl_s if lease_ttl_s is not None
                           else _env_f("RAY_TPU_LEASE_TTL_S",
                                       _LEASE_TTL_S))
        self._restart_timeout = _env_f(
            "RAY_TPU_HEAD_RESTART_TIMEOUT_S", _RESTART_TIMEOUT_S)
        self._restart_retry = _env_f(
            "RAY_TPU_HEAD_RESTART_RETRY_S", _RESTART_RETRY_S)
        self._nodes: Dict[str, NodeEntry] = {}
        # Durable tables behind the sharded-store interface
        # (tables.py): actor_id(bytes) -> info, (ns, name) -> actor_id,
        # (ns, key) -> value, pg_id -> {bundles, nodes}.  Reads take a
        # shard lock only; mutations additionally serialize on
        # self._lock (journal order == apply order).  Consistency
        # model, chosen deliberately: reads are READ-COMMITTED against
        # memory, not against the fsync — a lookup racing a mutation
        # may observe a value whose journal record has not hit disk
        # yet, and a crash in that window erases it.  The writer's own
        # ACK is the durability boundary (it ships only after the
        # fsync); cross-client read-then-crash anomalies are accepted,
        # as in the reference GCS's async-replicated Redis backing.
        self._actors = ShardedTable()
        self._named = ShardedTable()
        self._kv = ShardedTable()
        self._pgs = ShardedTable()
        self._spread_rr = 0
        # Delta-compressed resource sync: every entry change stamps a
        # monotonic view_seq; heartbeat replies carry only entries
        # newer than the caller's acked seq, plus death tombstones.
        # Membership-only changes keep the legacy counter for
        # book-keeping ("how many times did the set change").
        self._view_seq = 0
        self._view_floor = 0           # oldest seq tombstones cover
        self._view_gone: List[Tuple[int, str]] = []  # (seq, node_id)
        self._membership_version = 0
        # Lease epochs are minted from a counter that must survive
        # restarts (a zombie fenced before the crash must stay fenced
        # after replay), so it persists with the node table.
        self._epoch_counter = 0
        # (monotonic_ts, demand) of recent infeasible placements — the
        # autoscaler's scale-up signal.
        self._unmet_demands: List[Tuple[float, Dict[str, float]]] = []
        # Observability plane: per-node task-event stores + latest
        # metric snapshots shipped by the workers' EventShippers
        # (reference: GCS task-event aggregation, gcs_task_manager).
        # Bounded per node (drop-oldest) — event history is a window,
        # not a ledger.
        import collections as _collections
        import os as _os

        self._events_max = int(_os.environ.get(
            "RAY_TPU_HEAD_EVENTS_MAX", "100000"))
        # The node DIMENSION is bounded too: under autoscaler churn,
        # retired nodes must not pin event windows on the head forever.
        # Dead nodes' stores are kept (a killed worker's lane is
        # exactly what a post-mortem merged timeline needs) until the
        # cap forces out the stalest one.
        self._event_nodes_max = int(_os.environ.get(
            "RAY_TPU_HEAD_EVENT_NODES_MAX", "64"))
        self._node_events: Dict[str, Any] = {}
        self._node_event_meta: Dict[str, Dict[str, Any]] = {}
        self._node_metrics: Dict[str, Dict] = {}
        # Structured log plane: bounded per-node record stores fed by
        # the same push_events flushes (observability/logs.py).
        self._logs_max = int(_os.environ.get(
            "RAY_TPU_HEAD_LOGS_MAX", "50000"))
        self._node_logs: Dict[str, Any] = {}
        self._events_lock = threading.Lock()
        # Device-plane profile artifacts (zipped jax.profiler trace
        # bundles shipped by the node ``device_trace`` RPC): a
        # byte-capped drop-oldest store — artifacts are a download
        # window, not a ledger (observability/device.py).
        self._artifact_bytes_max = int(_os.environ.get(
            "RAY_TPU_HEAD_ARTIFACT_BYTES", str(64 << 20)))
        self._artifacts: "_collections.OrderedDict[str, Dict]" = \
            _collections.OrderedDict()
        self._artifacts_lock = threading.Lock()
        # Postmortem plane: typed death reports from process
        # supervisors (observability/postmortem.py), keyed by incident
        # id in a bounded drop-oldest window — the "why did it die"
        # record ActorDiedError contexts, `ray_tpu top`'s incidents
        # lane and the /api/postmortem route read back.
        self._death_reports_max = int(_os.environ.get(
            "RAY_TPU_HEAD_DEATH_REPORTS_MAX", "256"))
        self._death_reports: "_collections.OrderedDict[str, Dict]" = \
            _collections.OrderedDict()
        self._death_lock = threading.Lock()
        self._deque = _collections.deque
        # After a restart, actors replay before their nodes reattach:
        # give nodes one lease of grace before declaring them dead.
        self._replay_grace_until = 0.0
        # Mutating handlers dedup on client-minted idempotency keys:
        # a retried register/remove whose first RESPONSE was lost (rpc
        # chaos, head hiccup) replays the original reply instead of
        # re-applying (e.g. a spurious "name already taken").  The
        # cache persists through the journal, so the dedup window
        # spans a head restart.
        self._idem = IdempotencyCache()
        self._storage_path = storage_path
        self._persist_mode = (persist_mode or os.environ.get(
            "RAY_TPU_HEAD_PERSIST_MODE", "journal"))
        self._legacy_dirty = False
        self._log: Optional[journal_mod.JournalWriter] = None
        # Replicated-head role state (docs/fault_tolerance.md, "True
        # head HA").  Head GENERATIONS are cluster-scope fencing
        # tokens: the standby inherits the primary's at seed time and
        # mints gen+1 at promotion; a head holding an older generation
        # rejects every mutation typed (NotPrimaryError) — a deposed
        # primary can never ack again.
        self._standby_of = standby_of
        self._is_primary = standby_of is None
        self._deposed = False
        self._known_primary = standby_of or ""
        self._generation = 1
        self._applied_seq = 0   # standby: last journal seq applied
        self._repl_mode = (repl_mode or os.environ.get(
            "RAY_TPU_HEAD_REPL_MODE", "sync"))
        self._primary_ttl = (primary_ttl_s if primary_ttl_s is not None
                             else _env_f("RAY_TPU_HEAD_PRIMARY_TTL_S",
                                         self._lease_ttl))
        self._repl_timeout = (repl_timeout_s
                              if repl_timeout_s is not None
                              else _env_f("RAY_TPU_HEAD_REPL_TIMEOUT_S",
                                          5.0))
        self._primary_lease_expires = 0.0
        # Standby gate: repl traffic parks here until the seed applied.
        self._repl_ready = threading.Event()
        self._repl: Optional[ReplicationSender] = None
        self._recovered_seqno = 0
        self._resume_restarting: List[bytes] = []
        # Historical retention: size-capped on-disk rings next to the
        # journal absorb every event/log ingest, so timeline/log
        # queries with history=True outlive the bounded in-memory
        # windows (and a promoted standby can answer them — the
        # replication side-stream feeds ITS rings).
        self._events_ring: Optional[DiskRing] = None
        self._logs_ring: Optional[DiskRing] = None
        self._metrics_ring: Optional[DiskRing] = None
        if storage_path:
            retain = int(_env_f("RAY_TPU_HEAD_RETAIN_BYTES", 32 << 20))
            if retain > 0:
                self._events_ring = DiskRing(
                    storage_path + ".events", retain)
                self._logs_ring = DiskRing(
                    storage_path + ".logs", retain)
                self._metrics_ring = DiskRing(
                    storage_path + ".metrics", retain)
        # Metrics time-series store (observability/tsdb.py): every
        # push_events snapshot lands here as compressed history, the
        # metrics_query RPC answers windowed reads, and the alert
        # loop evaluates its rules against it.  Restart recovery
        # replays the on-disk metrics ring (same pattern as the
        # event/log rings; a promoted standby's ring was fed by the
        # replication side-stream, so it answers pre-failover
        # queries).
        self._tsdb = tsdb_mod.TSDB()
        if self._metrics_ring is not None:
            cutoff = time.time() - self._tsdb.retain_s
            for rec in self._metrics_ring.scan():
                try:
                    if float(rec.get("ts") or 0.0) >= cutoff:
                        self._tsdb.ingest(rec["node"], rec["state"],
                                          rec["ts"],
                                          rec.get("inc", ""))
                except (KeyError, TypeError, ValueError):
                    continue  # torn/foreign record: skip, keep rest
        if storage_path and not self._is_primary:
            # Standby: local state is stale by definition — it seeds
            # fresh from the primary below; _apply_seed folds the seed
            # into a local snapshot + fresh WAL.
            pass
        elif storage_path:
            self._recover()
            if self._persist_mode == "journal":
                self._log = journal_mod.JournalWriter(
                    storage_path, start_seqno=self._recovered_seqno)
            else:
                # journal → snapshot mode switch: fold the replayed
                # tail into a fresh snapshot, then drop the segments —
                # left behind, a later recovery would replay stale
                # records on top of newer snapshots.
                segs = journal_mod.list_segments(storage_path)
                if segs:
                    with self._lock:
                        state = self._state_locked()
                    journal_mod.write_snapshot(
                        storage_path, state, self._recovered_seqno)
                    for _idx, seg_path in segs:
                        try:
                            os.unlink(seg_path)
                        except OSError:
                            pass

        def _mut(fn):
            """Durable-mutation wrapper: idempotency dedup → epoch
            fence → handler → journal commit barrier (the reply must
            not ship before its redo records are fsync'd)."""

            def wrapped(payload):
                # Generation fence FIRST: a standby or deposed primary
                # must not ack (not even from the idempotency cache —
                # its cache may be behind the new primary's).
                self._check_primary_for_mutation(payload, fn.__name__)
                key = (payload.pop(IDEMPOTENCY_KEY, None)
                       if isinstance(payload, dict) else None)
                if key is None:
                    # Fence + apply under ONE critical section (RLock;
                    # the handler re-acquires reentrantly): the reaper
                    # cannot fence this epoch between the check and
                    # the write.  The fsync barrier stays outside the
                    # lock — durability ordering is fixed at append
                    # time, and an fsync under the table lock would
                    # stall every heartbeat behind the disk.
                    with self._lock:
                        self._fence(payload, fn.__name__)
                        reply = fn(payload)
                    self._commit_persist()
                    return reply
                while True:
                    hit, reply = self._idem.get(key)
                    if hit:
                        _rpc_metrics()["idem_hits"].inc(
                            tags={"method": fn.__name__})
                        # The cached reply must not ack ahead of the
                        # durability/replication barrier: the FIRST
                        # delivery may have journaled + cached but
                        # failed its sync-mode standby ack — a
                        # barrier-less cache hit here would ack a
                        # mutation a failover then loses.
                        self._commit_persist()
                        return reply
                    ev, mine = self._idem.claim(key)
                    if not mine:
                        # First delivery still executing: wait it out,
                        # then re-read (a RAISE cached nothing and the
                        # retry claims the key itself).
                        ev.wait(timeout=60.0)
                        continue
                    try:
                        with self._lock:
                            self._fence(payload, fn.__name__)
                            reply = fn(payload)
                            self._journal({"op": "idem", "key": key,
                                           "reply": reply})
                        self._idem.put(key, reply)
                        self._commit_persist()
                        return reply
                    finally:
                        self._idem.release(key)

            wrapped.__name__ = getattr(fn, "__name__", "mut")
            return wrapped

        self._server = RpcServer({
            "register_node": _mut(self._register_node),
            "heartbeat": self._heartbeat,
            "heartbeat_batch": self._heartbeat_batch,  # raylint: disable=rpc-protocol -- driven by tools/vcluster.py (the out-of-package virtual-cluster stress harness)
            "drain_node": _mut(self._drain_node),
            "list_nodes": self._list_nodes,
            "place": self._place,
            "kv_put": _mut(self._kv_put),
            "kv_get": self._kv_get,
            "kv_del": _mut(self._kv_del),
            "kv_keys": self._kv_keys,
            "register_actor": _mut(self._register_actor),
            "lookup_actor": self._lookup_actor,
            "lookup_named_actor": self._lookup_named_actor,
            "remove_actor": _mut(self._remove_actor),
            "list_actors": self._list_actors_rpc,
            "create_pg": _mut(self._create_pg),
            "remove_pg": _mut(self._remove_pg),
            # _mut although liveness-shaped: it retires actor entries
            # (durable-table writes that must journal + commit before
            # the reply) and duplicate peer reports dedup for free.
            "report_node_failure": _mut(self._report_node_failure),
            "pubsub_poll": self._pubsub_poll,
            "pending_demand": self._pending_demand,
            "push_events": self._push_events,
            "cluster_timeline": self._cluster_timeline,
            "cluster_metrics": self._cluster_metrics,
            "cluster_logs": self._cluster_logs,
            # Windowed metric history + alert plane (read surfaces:
            # CLI `ray_tpu metrics`, dashboard /api/metrics/query +
            # /api/alerts, tsdb.query_cluster).
            "metrics_query": self._metrics_query,
            # Device-trace artifact store (put: the node device_trace
            # RPC after a capture; get/list: CLI `ray_tpu profile
            # --device` and the dashboard /api/profile?device=1).
            "put_artifact": self._put_artifact,
            "get_artifact": self._get_artifact,
            "list_artifacts": self._list_artifacts,
            # Postmortem plane (put: the process supervisor after a
            # child death / `ray_tpu postmortem --capture`; get/list:
            # ActorDiedError enrichment, the postmortem CLI, `ray_tpu
            # top`'s incidents lane, dashboard /api/postmortem).
            "report_death": self._report_death,
            "get_death_report": self._get_death_report,
            "list_death_reports": self._list_death_reports,
            "alerts_status": self._alerts_status,
            "alert_rules": self._alert_rules,  # raylint: disable=rpc-protocol -- rule add/remove is driven by tests and ops tooling (out of package); the read surfaces ride metrics_query/alerts_status
            # Replicated-head protocol (replication.py is the caller
            # for the repl_* stream; promote/repl_status/repl_control
            # are driven by tools/vcluster.py and ops tooling).
            "standby_attach": self._standby_attach,
            "repl_frames": self._repl_frames,  # raylint: disable=journaled-mutation -- IS the replication apply path: records arrive journaled by the primary and land in this head's own WAL via append_replica before the ack
            "repl_heartbeat": self._repl_heartbeat,
            "repl_seed": self._repl_seed,  # raylint: disable=journaled-mutation -- full-snapshot re-seed: the state replaces the tables wholesale and is folded into a local snapshot + fresh WAL segment atomically
            "repl_events": self._repl_events,
            "repl_status": self._repl_status,  # raylint: disable=rpc-protocol -- driven by tools/vcluster.py and ops tooling (out of package)
            "repl_control": self._repl_control,  # raylint: disable=rpc-protocol -- chaos/ops hook driven by tools/vcluster.py (partition_heads, detach_standby)
            "promote": self._promote_rpc,  # raylint: disable=rpc-protocol -- driven by tools/vcluster.py promote() and failover runbooks (out of package)
            "ping": lambda p: "pong",  # raylint: disable=rpc-protocol -- liveness probe for out-of-package callers (tests, ops tooling, vcluster)
        }, host=host, port=port,
            # The replication stream is serialized by the sender's
            # ship lock and NEEDS arrival order; running it inline on
            # the connection reader also saves a thread spawn per
            # shipped batch — the hot path of every sync-mode ack.
            ordered={"repl_frames", "repl_heartbeat", "repl_events"})
        # Batched long-poll pubsub: node deaths and actor FSM
        # transitions fan out through one outstanding poll per
        # subscriber (src/ray/pubsub/README.md:1-44).
        from .pubsub import Publisher

        self._publisher = Publisher()
        self.address = self._server.address
        # Alert/SLO plane: declarative windowed rules evaluated over
        # the TSDB in a head loop; transitions fan out through the
        # "alerts" pubsub channel, a merged-timeline instant, a
        # ray_tpu.alerts log record, and the alerts_firing gauge.
        self._alert_eval_s = _env_f("RAY_TPU_ALERT_EVAL_S", 2.0)
        self._alerts = alerts_mod.AlertManager(
            self._tsdb, on_transition=self._on_alert_transition)
        for _rule in alerts_mod.default_rules():
            self._alerts.add_rule(_rule)
        self._alert_thread: Optional[threading.Thread] = None
        # Actor restart machinery (reference: gcs_actor_manager.h:308
        # FSM — ALIVE → RESTARTING → ALIVE/DEAD with max_restarts).
        self._pool = ClientPool()
        self._stop = threading.Event()
        self._restart_pending: List[bytes] = []
        self._restart_cond = threading.Condition(self._lock)
        self._restarter = threading.Thread(target=self._restart_loop,
                                           daemon=True)
        self._restarter.start()
        self._reaper = threading.Thread(target=self._reap_loop, daemon=True)
        self._reaper.start()
        if os.environ.get("RAY_TPU_ALERTS", "1").lower() not in (
                "0", "false"):
            self._alert_thread = threading.Thread(
                target=self._alert_loop, daemon=True,
                name="head-alerts")
            self._alert_thread.start()
        self._compactor: Optional[threading.Thread] = None
        if self._log is not None:
            self._ensure_compactor()
        resume = getattr(self, "_resume_restarting", None)
        if resume:
            with self._restart_cond:
                self._restart_pending.extend(resume)
                self._restart_cond.notify_all()
        self._standby_watch: Optional[threading.Thread] = None
        if not self._is_primary:
            # Standby boot: seed from the primary (registering our
            # address as its replication target), then watch its
            # lease — promotion fires when it lapses.
            self._seed_from_primary()
            self._standby_watch = threading.Thread(
                target=self._standby_watch_loop, daemon=True,
                name="head-standby-watch")
            self._standby_watch.start()
        _repl_metrics()["generation"].set(float(self._generation))

    def _ensure_compactor(self) -> None:
        if self._compactor is None and self._log is not None:
            self._compactor = threading.Thread(
                target=self._compact_loop, daemon=True)
            self._compactor.start()

    # ---------------------------------------------------- persistence
    def _journal(self, record: Dict[str, Any]) -> None:
        """Append one redo record at the MUTATION POINT (caller holds
        self._lock, so journal order == apply order).  Cheap — the
        durability barrier is the wrapper's ``_commit_persist``."""
        if self._log is not None:
            self._log.append(record)
        elif self._storage_path:
            self._legacy_dirty = True  # snapshot mode: rewrite on commit

    def _commit_persist(self) -> None:
        """Durability barrier before a mutation's reply ships: fsync
        the journal tail (one fsync amortizes every record the RPC
        produced) — or, in legacy snapshot mode, rewrite the whole
        snapshot (the seed behavior the bench compares against).
        With a standby attached in sync mode, the barrier ALSO waits
        for the standby's durable ack: an acked mutation is then on
        both disks, and failover loses nothing acked."""
        if self._log is not None:
            repl = self._repl
            active = (repl is not None and repl.attached
                      and self._is_primary and not self._deposed)
            if active:
                # Overlap: the background shipper puts the frames on
                # the wire while we fsync locally; the barrier then
                # usually finds its ack already absorbed.
                target = self._log.seqno
                repl.kick()
                self._log.commit()
                repl.commit_barrier(target)
            else:
                self._log.commit()
        elif self._storage_path and self._legacy_dirty:
            with self._lock:
                state = self._state_locked()
                self._legacy_dirty = False
            try:
                # Stamp the recovery seqno so a later journal-mode
                # boot never replays pre-switch records on top.
                journal_mod.write_snapshot(self._storage_path, state,
                                           self._recovered_seqno)
            except OSError:
                pass

    def _fence(self, payload, method: str) -> None:
        """Reject a mutation carrying a superseded lease epoch.  Only
        payloads that CARRY an epoch are fenced (raw/legacy callers and
        head-internal paths don't).  The caller's identity is
        ``epoch_node`` (falling back to ``node_id`` for node-scoped
        ops like drain)."""
        from ..exceptions import StaleEpochError

        if not isinstance(payload, dict):
            return
        sent = payload.get("epoch")
        if sent is None:
            return
        nid = payload.get("epoch_node") or payload.get("node_id") or ""
        with self._lock:
            entry = self._nodes.get(nid)
            current = entry.epoch if entry is not None else None
            ok = (entry is not None and entry.alive
                  and entry.epoch == sent)
        if not ok:
            _lease_metrics()["stale_rejections"].inc(
                tags={"method": method})
            raise StaleEpochError(
                "mutation fenced: lease epoch superseded (node was "
                "declared dead or never registered; re-register to "
                "obtain a fresh epoch)",
                node_id=nid, sent_epoch=sent, current_epoch=current,
                context={"method": method})

    # ---------------------------------------------------- replication
    @property
    def generation(self) -> int:
        return self._generation

    @property
    def deposed(self) -> bool:
        return self._deposed

    def journal_seqno(self) -> int:
        return (self._log.seqno if self._log is not None
                else self._recovered_seqno)

    def _head_set_list(self) -> List[str]:
        """Ordered candidate list clients should hold: believed
        primary first, then the standby."""
        if self._is_primary and not self._deposed:
            out = [self.address]
            if self._repl is not None and self._repl.attached:
                out.append(self._repl.standby_address)
            return out
        primary = self._known_primary or self._standby_of or ""
        return ([primary, self.address] if primary
                else [self.address])

    def _check_primary_for_mutation(self, payload, method: str) -> None:
        """Cluster-scope fencing token check, run before every durable
        mutation: (1) a client that has seen a NEWER head generation
        deposes this head on contact — fencing propagates through
        clients even while the heads are partitioned from each other;
        (2) a standby or deposed head rejects typed with a hint at the
        believed primary."""
        from ..exceptions import NotPrimaryError

        sent_gen = (payload.pop("head_gen", None)
                    if isinstance(payload, dict) else None)
        if sent_gen is not None and int(sent_gen) > self._generation:
            self._depose(int(sent_gen))
        if self._is_primary and not self._deposed:
            return
        _lease_metrics()["stale_rejections"].inc(
            tags={"method": method})
        raise NotPrimaryError(
            ("head deposed by a newer generation"
             if self._deposed else
             "standby head cannot ack mutations"),
            generation=self._generation,
            primary_hint=(self._known_primary
                          or self._standby_of or ""),
            context={"method": method})

    def _depose(self, gen: int, hint: str = "") -> None:
        """This head learned of a newer generation: it is no longer
        primary and must never ack a mutation again (zombie-write
        fencing at cluster scope).  Idempotent."""
        with self._lock:
            if self._deposed and gen <= self._generation:
                return
            self._deposed = True
            if hint:
                self._known_primary = hint
        import logging

        logging.getLogger("ray_tpu.head").warning(
            "head %s deposed: generation %d superseded by %d "
            "(new primary: %s)", self.address, self._generation,
            gen, hint or "unknown")

    def build_seed(self) -> Tuple[Dict[str, Any], int, int]:
        """(state, seqno, generation) snapshot for seeding a standby,
        captured atomically against the journal tap."""
        with self._lock:
            return (self._state_locked(), self.journal_seqno(),
                    self._generation)

    def _standby_attach(self, p):
        """A standby registered itself (payload: its address).  The
        reply carries the full seed; the state capture, watermark
        reset, and sender attach form ONE critical section against
        the journal tap, so every record past ``seqno`` reaches the
        standby as a frame and nothing is ever in neither."""
        if not self._is_primary or self._deposed:
            from ..exceptions import NotPrimaryError

            raise NotPrimaryError(
                "standby_attach on a non-primary head",
                generation=self._generation,
                primary_hint=self._known_primary or "")
        if self._log is None:
            return {"ok": False,
                    "error": "head HA requires journal persist mode "
                             "(construct the primary with a "
                             "storage_path and persist_mode="
                             "'journal')"}
        address = p["address"]
        with self._lock:
            if self._repl is None:
                self._repl = ReplicationSender(
                    self, self._repl_mode,
                    primary_ttl_s=self._primary_ttl,
                    sync_timeout_s=self._repl_timeout)
                self._log.set_tap(self._repl.offer)
            state = self._state_locked()
            seqno = self._log.seqno
            self._repl.attach(address, seqno)
        _repl_metrics()["standby_up"].set(1.0)
        return {"ok": True, "state": state, "seqno": seqno,
                "gen": self._generation,
                "mode": self._repl_mode,
                "primary_ttl_s": self._primary_ttl,
                "primary": self.address}

    def _seed_from_primary(self, deadline_s: float = 30.0) -> None:
        """Standby boot: attach to the primary and apply its seed.
        Retries transport failures under a deadline — a standby that
        cannot reach its primary at boot is a misconfiguration."""
        deadline = time.monotonic() + deadline_s
        last: Optional[Exception] = None
        while time.monotonic() < deadline:
            try:
                client = RpcClient(self._standby_of,
                                   connect_timeout=5.0)
                try:
                    resp = client.call(
                        "standby_attach", {"address": self.address},
                        timeout=max(10.0, self._repl_timeout))
                finally:
                    client.close()
                if not resp.get("ok"):
                    raise RuntimeError(resp.get("error") or
                                       "standby_attach rejected")
                self._apply_seed(resp["state"], resp["seqno"],
                                 resp["gen"])
                if resp.get("primary_ttl_s"):
                    self._primary_ttl = float(resp["primary_ttl_s"])
                self._known_primary = resp.get("primary",
                                               self._standby_of)
                return
            except (ConnectionError, TimeoutError, OSError) as e:
                last = e
                time.sleep(0.2)
        raise ConnectionError(
            f"standby could not seed from primary "
            f"{self._standby_of}: {last}")

    def _apply_seed(self, state: Dict[str, Any], seqno: int,
                    gen: int) -> None:
        """Replace local state with the primary's seed and fold it
        into a local snapshot + fresh WAL segment, so a promoted (or
        locally restarted) standby recovers from its OWN disk."""
        seqno = int(seqno)
        with self._lock:
            self._nodes.clear()
            self._load_state(state)
            self._generation = int(gen)
            self._recovered_seqno = seqno
            self._applied_seq = seqno
            if self._storage_path:
                if self._log is None:
                    journal_mod.write_snapshot(
                        self._storage_path, state, seqno)
                    # First boot as standby: any WAL left by a PRIOR
                    # life of this storage (e.g. a deposed ex-primary
                    # rejoining as standby) may hold a DIVERGED,
                    # never-acked tail past the seed seqno — a later
                    # local recovery would replay those zombie
                    # records on top of the seed.  The seed
                    # supersedes everything: drop the old segments.
                    for _idx, seg_path in journal_mod.list_segments(
                            self._storage_path):
                        try:
                            os.unlink(seg_path)
                        except OSError:
                            pass
                    self._log = journal_mod.JournalWriter(
                        self._storage_path, start_seqno=seqno)
                else:
                    # Mid-life re-seed (we fell behind the sender's
                    # buffer): rotate first so every pre-seed segment
                    # is droppable, then snapshot at the seed seqno.
                    new_seg = self._log.rotate()
                    journal_mod.write_snapshot(
                        self._storage_path, state, seqno)
                    self._log.drop_segments_before(new_seg)
                    self._log.advance_seqno(seqno)
            self._primary_lease_expires = (time.monotonic()
                                           + self._primary_ttl)
        self._ensure_compactor()
        _repl_metrics()["generation"].set(float(self._generation))
        self._repl_ready.set()

    def _repl_frames(self, p):
        """Standby tail: apply a run of journal frames, append them to
        the local WAL (primary seqnos preserved), fsync, then ack the
        durable watermark.  A torn tail in the payload acks only the
        complete prefix — the sender re-ships from the watermark.
        Generation rules: a frame stream from an OLDER generation than
        ours means we promoted past that primary — answer typed so it
        deposes itself."""
        from ..exceptions import NotPrimaryError

        gen = int(p.get("gen") or 0)
        if self._is_primary or gen < self._generation:
            raise NotPrimaryError(
                "replication frames from a superseded primary",
                generation=self._generation,
                primary_hint=self.address,
                context={"promoted": True})
        if not self._repl_ready.wait(timeout=10.0):
            return {"ok": False, "applied_seq": 0, "unseeded": True,
                    "gen": self._generation}
        records, _consumed, torn = journal_mod.parse_frames(
            p.get("frames") or b"")
        with self._lock:
            if gen > self._generation:
                self._generation = gen
            for rec in records:
                seq = int(rec.get("seq") or 0)
                if seq <= self._applied_seq:
                    continue  # duplicate re-ship after a lost ack
                if seq > self._applied_seq + 1:
                    # Gap (a pipelined batch raced a sender rewind):
                    # ack only the contiguous prefix — the sender
                    # re-ships from the watermark or re-seeds.
                    break
                self._apply_record(rec)
                if self._log is not None:
                    self._log.append_replica(rec)
                self._applied_seq = seq
            self._primary_lease_expires = (time.monotonic()
                                           + self._primary_ttl)
        if self._log is not None:
            # Flush (no fsync) before the ack: the record is already
            # fsync'd on the PRIMARY's disk, so single-fault zero-loss
            # holds; the watch loop fsyncs on its cadence so a
            # promoted standby's own WAL converges to durable.
            self._log.flush()
        return {"ok": True, "applied_seq": self._applied_seq,
                "gen": self._generation, "torn": bool(torn)}

    def _repl_heartbeat(self, p):
        """Idle-stream primary lease renewal + watermark exchange."""
        from ..exceptions import NotPrimaryError

        gen = int(p.get("gen") or 0)
        if self._is_primary or gen < self._generation:
            raise NotPrimaryError(
                "replication heartbeat from a superseded primary",
                generation=self._generation,
                primary_hint=self.address,
                context={"promoted": True})
        self._primary_lease_expires = (time.monotonic()
                                       + self._primary_ttl)
        return {"ok": True, "applied_seq": self._applied_seq,
                "gen": self._generation}

    def _repl_seed(self, p):
        """Mid-life full re-seed (standby fell behind the sender's
        buffer, or re-attached after a crash with a stale WAL)."""
        from ..exceptions import NotPrimaryError

        gen = int(p.get("gen") or 0)
        if self._is_primary or gen < self._generation:
            raise NotPrimaryError(
                "replication seed from a superseded primary",
                generation=self._generation,
                primary_hint=self.address,
                context={"promoted": True})
        self._apply_seed(p["state"], p["seqno"], gen)
        return {"ok": True, "applied_seq": self._applied_seq,
                "gen": self._generation}

    def _repl_events(self, p):
        """Observability side-stream: the primary forwards event/log
        flushes so this standby can answer timeline/log queries after
        promotion.  Reuses the push_events ingest wholesale."""
        return self._push_events(p)

    def _repl_status(self, p):
        """Role/generation/watermark introspection (vcluster, bench,
        runbooks).  ``{"digest": True}`` adds per-table content
        digests — the divergence probe the failover tests compare
        across the pair."""
        out: Dict[str, Any] = {
            "role": ("primary" if self._is_primary else "standby"),
            "deposed": self._deposed,
            "generation": self._generation,
            "address": self.address,
            "seqno": self.journal_seqno(),
            "applied_seq": self._applied_seq,
            "head_set": self._head_set_list(),
            "tables": {"kv": len(self._kv),
                       "actors": len(self._actors),
                       "named": len(self._named),
                       "pgs": len(self._pgs),
                       "nodes": len(self._nodes)},
        }
        if isinstance(p, dict) and p.get("digest"):
            out["digests"] = {"kv": self._kv.digest(),
                              "actors": self._actors.digest(),
                              "named": self._named.digest(),
                              "pgs": self._pgs.digest()}
        if self._repl is not None:
            repl = self._repl.status()
            out["repl"] = repl
            out["synced"] = (repl["lag_entries"] == 0
                            and repl["acked_seq"]
                            >= self.journal_seqno())
        if not self._is_primary:
            # Seed applied = synced (the watermark starts AT the seed
            # seqno — which is legitimately 0 on a fresh primary).
            out["synced"] = self._repl_ready.is_set()
            out["primary_lease_remaining_s"] = round(
                self._primary_lease_expires - time.monotonic(), 3)
        return out

    def _repl_control(self, p):
        """Chaos/ops hooks on the replication stream:
        ``{"partition_s": X}`` drops all repl traffic for X seconds
        (the standby sees a silent primary and promotes);
        ``{"detach_standby": True}`` dissolves the HA pair."""
        if p.get("partition_s") and self._repl is not None:
            self._repl.partition(float(p["partition_s"]))
        if p.get("detach_standby") and self._repl is not None:
            self._repl.detach()
        return {"ok": True}

    def _promote_rpc(self, p):
        return self.promote(reason=(p or {}).get("reason", "manual"))

    def promote(self, reason: str = "manual") -> Dict[str, Any]:
        """Standby → primary: mint generation+1 (the new fencing
        token), journal it, re-arm the lease grace window (nodes keep
        their replicated leases and reattach by heartbeat), and
        resume the restart/reap duties a standby held back."""
        with self._lock:
            if self._is_primary:
                return {"ok": True, "gen": self._generation,
                        "already_primary": True}
            self._is_primary = True
            self._deposed = False
            self._known_primary = self.address
            self._generation += 1
            self._journal({"op": "head_gen",
                           "gen": self._generation})
            # Nodes heartbeat the old address for a beat or two:
            # give them one lease of grace before reaping, exactly
            # like restart recovery.
            self._replay_grace_until = (time.monotonic()
                                        + self._lease_ttl)
            now = time.monotonic()
            for e in self._nodes.values():
                if e.alive:
                    e.last_heartbeat = now
                    e.lease_expires = now + self._lease_ttl
                    e.await_avail = True
            resume = [aid for aid, info in self._actors.items()
                      if info.get("state") == "RESTARTING"]
        self._commit_persist()
        m = _repl_metrics()
        m["failovers"].inc()
        m["generation"].set(float(self._generation))
        import logging

        logging.getLogger("ray_tpu.head").warning(
            "head %s promoted to primary (generation %d, %s)",
            self.address, self._generation, reason)
        self._publisher.publish("head_change", {
            "address": self.address,
            "generation": self._generation, "reason": reason})
        if resume:
            with self._restart_cond:
                self._restart_pending.extend(resume)
                self._restart_cond.notify_all()
        return {"ok": True, "gen": self._generation}

    def _standby_watch_loop(self):
        """Promotion timer: the primary's lease is renewed by every
        frame/heartbeat it ships; when it lapses for one primary TTL,
        this standby takes over."""
        poll = max(0.05, min(0.25, self._primary_ttl / 4))
        while not self._stop.wait(poll):
            if self._is_primary:
                return
            if not self._repl_ready.is_set():
                continue
            if self._log is not None:
                # Cadence fsync of the tailed WAL (acks only flush).
                self._log.commit()
            if time.monotonic() > self._primary_lease_expires:
                self.promote(reason="primary lease lapsed")
                return

    def _state_locked(self) -> Dict[str, Any]:
        """Serializable durable state (caller holds self._lock)."""
        return {
            "kv": self._kv.snapshot(),
            "named": self._named.snapshot(),
            "actors": {aid: dict(info)
                       for aid, info in self._actors.items()},
            "pgs": self._pgs.snapshot(),
            "nodes": {e.node_id: {
                "address": e.address, "total": dict(e.total),
                "labels": dict(e.labels), "name": e.name,
                "lease_id": e.lease_id, "epoch": e.epoch,
                "alive": e.alive,
            } for e in self._nodes.values()},
            "epoch_counter": self._epoch_counter,
            "head_gen": self._generation,
            "idem": self._idem.export(),
        }

    def _load_state(self, state: Dict[str, Any]) -> None:
        self._kv.replace_all(state.get("kv") or {})
        self._named.replace_all(state.get("named") or {})
        self._actors.replace_all(state.get("actors") or {})
        self._pgs.replace_all(state.get("pgs") or {})
        self._epoch_counter = int(state.get("epoch_counter") or 0)
        self._generation = max(self._generation,
                               int(state.get("head_gen") or 1))
        self._idem.load(state.get("idem") or {})
        now = time.monotonic()
        for nid, rec in (state.get("nodes") or {}).items():
            entry = NodeEntry(nid, rec["address"], rec["total"],
                              dict(rec.get("labels") or {}),
                              rec.get("name", ""),
                              lease_id=rec.get("lease_id", ""),
                              epoch=int(rec.get("epoch") or 0))
            entry.alive = bool(rec.get("alive", True))
            entry.last_heartbeat = now
            entry.lease_expires = now + self._lease_ttl
            entry.await_avail = True
            self._nodes[nid] = entry
            self._epoch_counter = max(self._epoch_counter, entry.epoch)

    def _apply_record(self, rec: Dict[str, Any]) -> None:
        """Redo one journal record against the tables (recovery path —
        no publishes, no re-journaling).  Records are state DELTAS, so
        replay is deterministic regardless of what the cluster looked
        like when the original RPC ran."""
        op = rec.get("op")
        if op == "kv_put":
            self._kv.put((rec["ns"], rec["key"]), rec["value"])
        elif op == "kv_del":
            self._kv.pop((rec["ns"], rec["key"]))
        elif op == "actor_put":
            info = dict(rec["info"])
            self._actors.put(rec["actor_id"], info)
            if info.get("name"):
                self._named.put(
                    (info.get("namespace", ""), info["name"]),
                    rec["actor_id"])
        elif op == "actor_del":
            info = self._actors.pop(rec["actor_id"])
            if info and info.get("name"):
                self._named.pop(
                    (info.get("namespace", ""), info["name"]))
        elif op == "pg_put":
            self._pgs.put(rec["pg_id"], {"bundles": rec["bundles"],
                                         "nodes": rec["nodes"]})
        elif op == "pg_del":
            self._pgs.pop(rec["pg_id"])
        elif op == "node_put":
            entry = NodeEntry(rec["node_id"], rec["address"],
                              rec["resources"],
                              dict(rec.get("labels") or {}),
                              rec.get("name", ""),
                              lease_id=rec.get("lease_id", ""),
                              epoch=int(rec.get("epoch") or 0))
            entry.await_avail = True
            self._nodes[rec["node_id"]] = entry
            self._epoch_counter = max(self._epoch_counter, entry.epoch)
        elif op == "node_res":
            entry = self._nodes.get(rec["node_id"])
            if entry is not None:
                for k, v in (rec.get("add") or {}).items():
                    entry.total[k] = entry.total.get(k, 0) + v
                    entry.available[k] = entry.available.get(k, 0) + v
                for k in rec.get("remove") or ():
                    entry.total.pop(k, None)
                    entry.available.pop(k, None)
        elif op == "node_dead":
            entry = self._nodes.get(rec["node_id"])
            if entry is not None:
                entry.alive = False  # epoch stays fenced
        elif op == "node_del":
            self._nodes.pop(rec["node_id"], None)
        elif op == "head_gen":
            # Promotion fencing token: the counter only climbs.
            self._generation = max(self._generation,
                                   int(rec.get("gen") or 1))
        elif op == "idem":
            self._idem.put(rec["key"], rec["reply"])

    def _recover(self) -> None:
        """Snapshot + journal-tail replay (gcs_init_data.h analogue).
        A torn last record is discarded by the segment reader — it was
        never acked.  Replayed nodes get one lease of grace to reattach
        before the reaper treats them as dead."""
        state, snap_seq = journal_mod.load_snapshot(self._storage_path)
        if state:
            self._load_state(state)
        last_seq, replayed = snap_seq, 0
        for _idx, path in journal_mod.list_segments(self._storage_path):
            for rec in journal_mod.read_segment(path):
                seq = int(rec.get("seq") or 0)
                if seq <= snap_seq:
                    continue  # the snapshot already folded this in
                self._apply_record(rec)
                last_seq = max(last_seq, seq)
                replayed += 1
        if replayed:
            journal_mod._journal_metrics()["replayed"].inc(replayed)
        self._recovered_seqno = last_seq
        self._resume_restarting = []
        had_any = bool(state) or replayed
        for aid, info in self._actors.items():
            info.pop("restart_deadline", None)
            if info.get("state") == "RESTARTING":
                # Mid-restart at crash time: re-enqueue once the
                # restart loop exists (gcs_init_data replay semantics).
                self._resume_restarting.append(aid)
        if had_any:
            # Lease-derived grace (was a hardcoded 15 s): nodes get
            # exactly one lease TTL to reattach after a head restart.
            self._replay_grace_until = (time.monotonic()
                                        + self._lease_ttl)

    # ---------------------------------------------------- compaction
    def _compact_loop(self):
        every = _env_f("RAY_TPU_HEAD_COMPACT_EVERY_S", _COMPACT_EVERY_S)
        max_bytes = int(_env_f("RAY_TPU_HEAD_COMPACT_BYTES",
                               _COMPACT_BYTES))
        last = time.monotonic()
        while not self._stop.wait(min(1.0, every / 4)):
            due = (time.monotonic() - last >= every
                   or self._log.bytes_since_rotate >= max_bytes)
            if not due:
                continue
            try:
                self.compact()
            except OSError:
                pass  # disk hiccup: the journal still has everything
            last = time.monotonic()

    def compact(self) -> int:
        """Fold the journal into a snapshot; returns the snapshot's
        seqno.  Safe against concurrent mutations: state + seqno are
        captured and the journal rotated under the table lock, so
        every record racing the snapshot lands in the NEW segment with
        a seqno the snapshot doesn't cover, and replay applies it on
        top."""
        if self._log is None:
            raise RuntimeError("compaction requires journal mode")
        with self._lock:
            state = self._state_locked()
            seqno = self._log.seqno
            new_segment = self._log.rotate()
        journal_mod.write_snapshot(self._storage_path, state, seqno)
        self._log.drop_segments_before(new_segment)
        journal_mod._journal_metrics()["compactions"].inc()
        return seqno

    # ------------------------------------------------------------- nodes
    def _next_view_seq(self) -> int:
        self._view_seq += 1
        return self._view_seq

    def _register_node(self, p):
        """Mint a lease: (lease_id, epoch).  A RE-registration (same
        node_id — zombie reattach, post-restart handshake) supersedes
        the previous lease: the new epoch is strictly newer and every
        write still carrying the old one is fenced."""
        with self._lock:
            self._epoch_counter += 1
            epoch = self._epoch_counter
            lease_id = uuid.uuid4().hex
            entry = NodeEntry(p["node_id"], p["address"],
                              p["resources"], p.get("labels", {}),
                              p.get("name", ""),
                              lease_id=lease_id, epoch=epoch)
            entry.lease_expires = time.monotonic() + self._lease_ttl
            entry.view_seq = self._next_view_seq()
            self._nodes[p["node_id"]] = entry
            self._membership_version += 1
            self._journal({"op": "node_put", "node_id": p["node_id"],
                           "address": p["address"],
                           "resources": dict(p["resources"]),
                           "labels": dict(p.get("labels") or {}),
                           "name": p.get("name", ""),
                           "lease_id": lease_id, "epoch": epoch})
        _lease_metrics()["grants"].inc()
        return {"ok": True, "num_nodes": len(self._nodes),
                "lease_id": lease_id, "epoch": epoch,
                "lease_ttl_s": self._lease_ttl,
                "head_gen": self._generation,
                "head_set": self._head_set_list()}

    def _heartbeat_one(self, p) -> Dict[str, Any]:
        """One node's beat: lease renewal + availability delta absorb.
        Caller holds self._lock.  Replies {"ok": False, "reregister":
        True} for unknown nodes, fenced epochs, and revoked leases —
        the client re-registers and mints a fresh epoch."""
        entry = self._nodes.get(p["node_id"])
        if entry is None:
            return {"ok": False, "reregister": True}
        sent_epoch = p.get("epoch")
        if sent_epoch is not None and sent_epoch != entry.epoch:
            _lease_metrics()["stale_heartbeats"].inc()
            return {"ok": False, "reregister": True}
        if not entry.alive:
            # Declared dead = lease revoked.  No resurrect-in-place
            # (the seed behavior): the node must re-register so its
            # old epoch stays fenced — zombie writes in flight get
            # StaleEpochError instead of landing.
            if sent_epoch is not None:
                _lease_metrics()["stale_heartbeats"].inc()
            return {"ok": False, "reregister": True}
        now = time.monotonic()
        entry.last_heartbeat = now
        entry.lease_expires = now + self._lease_ttl
        _lease_metrics()["renewals"].inc()
        if "available" in p:
            if p["available"] != entry.available:
                entry.available = dict(p["available"])
                entry.view_seq = self._next_view_seq()
            entry.await_avail = False
        if "add_resources" in p:
            for k, v in p["add_resources"].items():
                entry.total[k] = entry.total.get(k, 0) + v
                entry.available[k] = entry.available.get(k, 0) + v
            # Totals changed: stale cached views must refetch them.
            self._membership_version += 1
            entry.view_seq = self._next_view_seq()
            # Dynamic totals (PG synthetic capacity) are DURABLE
            # state riding the heartbeat path: journal them, or a
            # head restart replays registration-time totals and every
            # bundle-resource placement goes infeasible forever.
            self._journal({"op": "node_res", "node_id": p["node_id"],
                           "add": dict(p["add_resources"])})
        if "remove_resources" in p:
            for k in p["remove_resources"]:
                entry.total.pop(k, None)
                entry.available.pop(k, None)
            self._membership_version += 1
            entry.view_seq = self._next_view_seq()
            self._journal({"op": "node_res", "node_id": p["node_id"],
                           "remove": list(p["remove_resources"])})
        reply = {"ok": True, "epoch": entry.epoch,
                 "lease_ttl_s": self._lease_ttl,
                 "head_gen": self._generation}
        if entry.await_avail:
            # Journal-replayed entry: the head has registration-time
            # totals but no live availability — ask for a full report.
            reply["need_available"] = True
        return reply

    def _view_payload_locked(self, client_seq) -> Dict[str, Any]:
        """Resource-view sync, hub-routed and DELTA-COMPRESSED
        (reference: ray_syncer.h:83 — per-node views fan out through
        the GCS hub).  ``client_seq`` None (or older than the tombstone
        ring covers) gets the full view; otherwise only entries whose
        view_seq advanced past it, plus death tombstones.  Dead nodes
        are excluded from views — they'd grow the payload forever
        under churn."""
        out: Dict[str, Any] = {"view_seq": self._view_seq}

        def rec(e: NodeEntry) -> Dict[str, Any]:
            return {"available": dict(e.available),
                    "total": dict(e.total), "alive": True}

        if (client_seq is None or client_seq < self._view_floor
                or client_seq > self._view_seq):
            # ``client_seq > _view_seq``: a cursor minted against
            # ANOTHER head's sequence space (the node failed over to
            # a promoted standby) — resync with a full view, same as
            # the pubsub cursor clamp.
            out["view_full"] = {e.node_id: rec(e)
                                for e in self._nodes.values() if e.alive}
            return out
        delta = {e.node_id: rec(e) for e in self._nodes.values()
                 if e.alive and e.view_seq > client_seq}
        if delta:
            out["view_delta"] = delta
        removed = [nid for seq, nid in self._view_gone
                   if seq > client_seq
                   and not (nid in self._nodes
                            and self._nodes[nid].alive)]
        if removed:
            out["view_removed"] = removed
        return out

    def _tombstone_locked(self, node_id: str) -> None:
        """Record a death for delta sync; clients behind the ring's
        floor fall back to a full view."""
        seq = self._next_view_seq()
        self._view_gone.append((seq, node_id))
        while len(self._view_gone) > 1024:
            floor_seq, _nid = self._view_gone.pop(0)
            self._view_floor = floor_seq

    def _heartbeat(self, p):
        if not self._is_primary or self._deposed:
            # Pre-promotion standby: do NOT answer ``reregister`` (a
            # re-registration would be refused typed anyway) — the
            # client keeps beating and lands once we promote or it
            # fails back over to the primary.  A DEPOSED primary
            # additionally says so: its nodes must fail over NOW, or
            # the new primary's reaper fences their leases while
            # they beat a fenced head believing themselves healthy.
            return {"ok": False, "standby": True,
                    "deposed": self._deposed,
                    "head_gen": self._generation,
                    "head_set": self._head_set_list()}
        with self._lock:
            reply = self._heartbeat_one(p)
            # The one-off PG-capacity calls carry no view_seq field
            # and skip the view assembly entirely (seed behavior).
            if reply.get("ok") and "view_seq" in p:
                reply.update(self._view_payload_locked(p.get("view_seq")))
        # No-op unless the beat journaled a resource delta.
        self._commit_persist()
        return reply

    def _heartbeat_batch(self, p):
        """Many nodes' beats in ONE RPC (the vcluster harness
        multiplexes hundreds of virtual nodes per process): per-node
        replies plus a single shared view payload — at 300 nodes this
        collapses 300 round-trips and 300 view assemblies per interval
        into one of each."""
        if not self._is_primary or self._deposed:
            return {"ok": False, "standby": True,
                    "deposed": self._deposed,
                    "head_gen": self._generation,
                    "head_set": self._head_set_list(), "replies": []}
        replies = []
        with self._lock:
            for beat in p.get("beats") or ():
                replies.append(self._heartbeat_one(beat))
            out: Dict[str, Any] = {"ok": True, "replies": replies}
            if "view_seq" in p:
                out.update(self._view_payload_locked(p.get("view_seq")))
        self._commit_persist()
        return out

    def _drain_node(self, p):
        with self._lock:
            entry = self._nodes.pop(p["node_id"], None)
            if entry is not None:
                self._journal({"op": "node_del",
                               "node_id": p["node_id"]})
                self._tombstone_locked(p["node_id"])
            self._forget_actors_on(p["node_id"])
        if entry is not None:
            self._publish_node_death(p["node_id"], entry.address)
        return {"ok": entry is not None}

    def _report_node_failure(self, p):
        """A peer observed a broken connection to this node.  Marking
        it dead revokes its lease (fences its epoch): the node can only
        come back through re-registration, and writes carrying the old
        epoch are rejected typed."""
        with self._lock:
            entry = self._nodes.get(p["node_id"])
            was_alive = entry is not None and entry.alive
            if was_alive:
                entry.alive = False
                self._membership_version += 1
                self._journal({"op": "node_dead",
                               "node_id": p["node_id"]})
                self._tombstone_locked(p["node_id"])
            dead_actors = self._forget_actors_on(p["node_id"])
        if was_alive:
            self._publish_node_death(p["node_id"], entry.address)
        return {"ok": True, "dead_actors": dead_actors}

    def _pending_demand(self, p):
        """Unmet placement demands within the last ``window_s`` seconds
        (autoscaler input; reference: GcsAutoscalerStateManager's
        cluster resource state)."""
        window = float(p.get("window_s", 10.0))
        cutoff = time.monotonic() - window
        with self._lock:
            self._unmet_demands = [
                (t, d) for t, d in self._unmet_demands if t > cutoff]
            return [d for _t, d in self._unmet_demands]

    def _pubsub_poll(self, p):
        return self._publisher.poll(p.get("cursors", {}),
                                    timeout_s=min(60.0, float(
                                        p.get("timeout_s", 30.0))))

    # ------------------------------------------------- observability plane
    def _push_events(self, p):
        """Ingest one node's task-event batch + metric snapshot (the
        worker-side EventShipper's flush target).  Per-node stores are
        bounded drop-oldest rings, mirroring the worker buffers."""
        node_id = p["node_id"]
        events = p.get("events") or []
        records = p.get("logs") or []
        for r in records:
            # Stamp the origin node ONCE at ingest (cheaper than every
            # worker resolving it per record on its emit path).
            r.setdefault("node", node_id)
        # Unwrap the metrics snapshot: new shippers send
        # {ts, incarnation, state} (metrics.export_snapshot); a bare
        # state dict is a legacy/raw-push snapshot, stamped with
        # arrival time and no incarnation (rate() then falls back to
        # value-drop reset detection).
        m = p.get("metrics")
        m_state = m_ts = None
        m_inc = ""
        if isinstance(m, dict) and "incarnation" in m \
                and isinstance(m.get("state"), dict):
            m_state = m["state"]
            m_ts = float(m.get("ts") or time.time())
            m_inc = str(m["incarnation"])
        elif m is not None:
            m_state, m_ts = m, time.time()
        with self._events_lock:
            store = self._node_events.get(node_id)
            if store is None:
                store = self._node_events[node_id] = self._deque(
                    maxlen=self._events_max)
                self._prune_event_nodes_locked(keep=node_id)
            store.extend(events)
            if records:
                log_store = self._node_logs.get(node_id)
                if log_store is None:
                    log_store = self._node_logs[node_id] = self._deque(
                        maxlen=self._logs_max)
                log_store.extend(records)
            meta = self._node_event_meta.setdefault(node_id, {})
            meta["pid"] = p.get("pid")
            meta["node_dropped"] = int(p.get("dropped") or 0)
            meta["logs_dropped"] = int(p.get("logs_dropped") or 0)
            meta["received"] = meta.get("received", 0) + len(events)
            meta["logs_received"] = (meta.get("logs_received", 0)
                                     + len(records))
            meta["ts"] = time.monotonic()
            if m_state is not None:
                self._node_metrics[node_id] = m_state
                meta["metrics_ts"] = time.monotonic()
                meta["flush_s"] = p.get("flush_s")
        # Historical retention: every ingest also lands in the
        # size-capped disk rings next to the journal (history=True
        # queries outlive the bounded in-memory windows).
        if self._events_ring is not None and events:
            # Stamp the origin node on the ring copy (shallow): the
            # disk view has no per-node store dimension to recover it
            # from.
            self._events_ring.append_many(
                [{**e, "node": node_id} for e in events])
        if self._logs_ring is not None and records:
            self._logs_ring.append_many(records)
        if m_state is not None:
            # Time-series ingest + on-disk metrics ring (outside the
            # store lock: the TSDB serializes itself, and the ring
            # write must not stall concurrent event queries).
            self._tsdb.ingest(node_id, m_state, m_ts, m_inc)
            if self._metrics_ring is not None:
                self._metrics_ring.append_many([
                    {"node": node_id, "ts": m_ts, "inc": m_inc,
                     "state": m_state}])
        # Observability side-stream to the standby (best-effort,
        # bounded, never blocks this ack): a promoted standby can
        # answer timeline/log queries about the pre-failover cluster.
        repl = self._repl
        if repl is not None and repl.attached and self._is_primary:
            repl.offer_events(dict(p))
        if records:
            # Follow-mode fanout: one pubsub batch per ingested flush
            # (`ray_tpu logs -f` long-polls the "logs" channel).  A
            # SHORT replay ring: each batch can hold up to BATCH_MAX
            # records, and the authoritative store is _node_logs — a
            # follower further behind re-syncs via cluster_logs, so
            # an unsubscribed channel must not pin megabytes of
            # records at the default 1000-batch retention.
            self._publisher.publish("logs", {"node_id": node_id,
                                             "records": records},
                                    retain=32)
        return {"ok": True, "stored": len(events)}

    def _cluster_logs(self, p):
        """SERVER-SIDE-filtered log query over every node's record
        store (filters: trace_id, node, actor, level, logger, since/
        until, text, limit — observability.logs.filter_records is the
        one implementation)."""
        from ..observability.logs import filter_records

        p = dict(p or {})
        limit = int(p.pop("limit", 1000) or 1000)
        history = bool(p.pop("history", False))
        known = {"trace_id", "node", "actor", "level", "logger",
                 "since", "until", "text"}
        filters = {k: v for k, v in p.items()
                   if k in known and v is not None}
        if history and self._logs_ring is not None:
            # The on-disk ring: a longer window than the in-memory
            # store (size-capped in bytes, not records), same filters.
            records = list(self._logs_ring.scan())
        else:
            with self._events_lock:
                records = [r for store in self._node_logs.values()
                           for r in store]
        out = filter_records(records, limit=limit, **filters)
        return {"records": out, "total_stored": len(records)}

    def _prune_event_nodes_locked(self, keep: str) -> None:
        """Hold the node dimension at its cap: evict the
        longest-silent node's store — preferring nodes no longer
        registered alive — so churn can't grow head memory without
        bound.  Caller holds _events_lock."""
        while len(self._node_events) > self._event_nodes_max:
            def staleness(nid: str):
                alive = (nid in self._nodes
                         and self._nodes[nid].alive)
                return (alive,
                        self._node_event_meta.get(nid, {}).get("ts", 0))

            victim = min((n for n in self._node_events if n != keep),
                         key=staleness, default=None)
            if victim is None:
                return
            self._node_events.pop(victim, None)
            self._node_event_meta.pop(victim, None)
            self._node_metrics.pop(victim, None)
            self._node_logs.pop(victim, None)

    def _cluster_timeline(self, p):
        """The merged event store: every node's shipped events in one
        list (each process keeps its own Chrome-trace pid lane)."""
        node_id = p.get("node_id") if isinstance(p, dict) else None
        with_logs = (p.get("with_logs", True) if isinstance(p, dict)
                     else True)
        history = (p.get("history", False) if isinstance(p, dict)
                   else False)
        if history and self._events_ring is not None:
            # Disk-ring view: the size-capped window that outlives
            # RAY_TPU_HEAD_EVENTS_MAX (post-mortems; a promoted
            # standby serves its side-stream-fed copy).
            events = [e for e in self._events_ring.scan()
                      if node_id is None
                      or e.get("node") == node_id]
            records = [r for r in self._logs_ring.scan()
                       if node_id is None
                       or r.get("node") == node_id] \
                if (with_logs and self._logs_ring is not None) else []
            with self._events_lock:
                nodes = list(self._node_events)
                meta = {nid: dict(m)
                        for nid, m in self._node_event_meta.items()}
            if records:
                from ..observability.logs import to_timeline_events

                events = events + to_timeline_events(records)
            return {"events": events, "nodes": nodes, "meta": meta,
                    "history": True}
        with self._events_lock:
            if node_id is not None:
                events = list(self._node_events.get(node_id, ()))
                nodes = [node_id] if node_id in self._node_events else []
                records = list(self._node_logs.get(node_id, ())) \
                    if with_logs else []
            else:
                events = [e for store in self._node_events.values()
                          for e in store]
                nodes = list(self._node_events)
                records = [r for store in self._node_logs.values()
                           for r in store] if with_logs else []
            meta = {nid: dict(m)
                    for nid, m in self._node_event_meta.items()}
        if records:
            # Log records interleave with spans as instant events on
            # their process's lane: a trace id links spans ↔ logs in
            # ONE merged view.
            from ..observability.logs import to_timeline_events

            events = events + to_timeline_events(records)
        return {"events": events, "nodes": nodes, "meta": meta}

    def _cluster_metrics(self, _p):
        """Latest per-node metric snapshots ({node_id: export_state})
        for the aggregated /metrics exposition.  STALENESS-AWARE: a
        node whose last snapshot is older than
        ``RAY_TPU_METRICS_STALE_FACTOR`` of its own flush interval is
        dropped from the live exposition — a dead node's final
        snapshot must not export as live values forever (its history
        stays queryable through ``metrics_query``)."""
        factor = _env_f("RAY_TPU_METRICS_STALE_FACTOR", 5.0)
        now = time.monotonic()
        head_pid = os.getpid()
        hosted = False   # does a LIVE shipper cover this process?
        out: Dict[str, Dict] = {}
        with self._events_lock:
            for nid, state in self._node_metrics.items():
                meta = self._node_event_meta.get(nid) or {}
                ts = meta.get("metrics_ts")
                flush_s = float(meta.get("flush_s") or 1.0)
                if (factor > 0 and ts is not None
                        and now - ts > factor * max(flush_s, 0.05)):
                    continue
                if meta.get("pid") == head_pid:
                    hosted = True
                out[nid] = state
        if not hosted:
            # Standalone head process (no EventShipper of its own —
            # `ray_tpu start --head`): export its registry too, else
            # the journal/lease/replication/alert series it mints are
            # invisible to the aggregated exposition.  When the head
            # rides the driver process, that driver's shipper already
            # covers the shared registry.
            from ..observability import metrics as _metrics

            out["__head__"] = _metrics.export_state()
        return out

    # ------------------------------------------- metric history + alerts
    def _metrics_query(self, p):
        """Windowed TSDB query (read-only; standbys answer too — the
        replication side-stream feeds their store, so a promoted
        standby serves pre-failover history).  ``{"expr": ...}``
        evaluates one expression; ``{"names": true}`` lists stored
        series names + store stats instead."""
        p = p or {}
        if p.get("names"):
            return {"names": self._tsdb.series_names(),
                    "stats": self._tsdb.stats()}
        return self._tsdb.query(p.get("expr", ""))

    # ----------------------------------------- device-trace artifacts
    def _put_artifact(self, p):
        """Store one profile artifact (device-trace zip) in the
        byte-capped drop-oldest window.  Re-putting a name replaces
        it (a retried ship must not double-count the cap)."""
        name = str(p["name"])
        data = p.get("data") or b""
        meta = dict(p.get("meta") or {})
        meta.setdefault("ts", time.time())
        meta["bytes"] = len(data)
        with self._artifacts_lock:
            self._artifacts.pop(name, None)
            self._artifacts[name] = {"data": data, "meta": meta}
            total = sum(a["meta"]["bytes"]
                        for a in self._artifacts.values())
            while total > self._artifact_bytes_max \
                    and len(self._artifacts) > 1:
                _old, dropped = self._artifacts.popitem(last=False)
                total -= dropped["meta"]["bytes"]
        return {"ok": True, "name": name, "bytes": len(data)}

    def _get_artifact(self, p):
        name = str(p.get("name", ""))
        with self._artifacts_lock:
            art = self._artifacts.get(name)
            if art is None:
                return {"found": False}
            return {"found": True, "name": name,
                    "data": art["data"], "meta": dict(art["meta"])}

    def _list_artifacts(self, _p):
        with self._artifacts_lock:
            return [{"name": name, **a["meta"]}
                    for name, a in self._artifacts.items()]

    # ------------------------------------------------ postmortem plane
    def _report_death(self, p):
        """Ingest one typed death report (the supervisor's verdict:
        signal, exit code, OOM evidence, bundle name, last logs) and
        fan it out on the ``death_report`` pubsub channel so every
        node's error contexts can name the cause.  Ephemeral
        observability state like the artifact store: bounded, not
        journaled."""
        report = dict(p.get("report") or {})
        incident = str(report.get("incident") or "")
        if not incident:
            return {"ok": False}
        report.setdefault("ts", time.time())
        with self._death_lock:
            self._death_reports.pop(incident, None)
            self._death_reports[incident] = report
            while len(self._death_reports) > self._death_reports_max:
                self._death_reports.popitem(last=False)
        self._publisher.publish("death_report", dict(report),
                                retain=64)
        return {"ok": True, "incident": incident}

    def _get_death_report(self, p):
        """Lookup by incident id, by node id (newest first), or — with
        neither — the most recent report of all."""
        p = p or {}
        incident = p.get("incident")
        node_id = p.get("node_id")
        with self._death_lock:
            if incident:
                report = self._death_reports.get(str(incident))
                return ({"found": True, "report": dict(report)}
                        if report else {"found": False})
            for report in reversed(self._death_reports.values()):
                if not node_id or report.get("node_id") == node_id:
                    return {"found": True, "report": dict(report)}
        return {"found": False}

    def _list_death_reports(self, p):
        limit = int((p or {}).get("limit", 64))
        with self._death_lock:
            reports = [dict(r) for r in
                       reversed(self._death_reports.values())]
        return {"reports": reports[:limit]}

    def _crash_counts(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        with self._death_lock:
            for r in self._death_reports.values():
                nid = r.get("node_id") or ""
                if nid and r.get("cause") not in ("manual-capture",):
                    counts[nid] = counts.get(nid, 0) + 1
        return counts

    def _alerts_status(self, _p):
        """Declared rules + currently pending/firing instances."""
        return self._alerts.status()

    def _alert_rules(self, p):
        """Rule management: {"action": "add", "rule": {...}} /
        {"action": "remove", "name": ...} / default: list."""
        p = p or {}
        action = p.get("action", "list")
        if action == "add":
            rule = alerts_mod.AlertRule.from_dict(p["rule"])
            self._alerts.add_rule(rule)
            return {"ok": True, "rule": rule.to_dict()}
        if action == "remove":
            return {"ok": self._alerts.remove_rule(p["name"])}
        return {"rules": self._alerts.rules()}

    def _alert_loop(self):
        """Evaluate the rule set every RAY_TPU_ALERT_EVAL_S seconds.
        Standbys and deposed primaries keep their state machines
        quiet — after promotion the new primary's loop takes over
        against its side-stream-fed TSDB."""
        while not self._stop.wait(self._alert_eval_s):
            if not self._is_primary or self._deposed:
                continue
            self._alerts.evaluate()

    def _on_alert_transition(self, ev: Dict[str, Any]) -> None:
        """Fan one firing/cleared transition out: pubsub channel +
        merged-timeline instant on the head's own lane (the gauge and
        the ray_tpu.alerts log record are emitted by AlertManager)."""
        self._publisher.publish("alerts", dict(ev), retain=256)
        instant = {"name": f"alert:{ev['rule']}", "ph": "i", "s": "p",
                   "pid": f"head-{os.getpid()}", "tid": "alerts",
                   "ts": float(ev["ts"]) * 1e6,
                   "args": {"state": ev["state"], "value": ev["value"],
                            "labels": ev["labels"],
                            "threshold": ev["threshold"],
                            "alert": True}}
        with self._events_lock:
            store = self._node_events.get("__head__")
            if store is None:
                store = self._node_events["__head__"] = self._deque(
                    maxlen=self._events_max)
                self._prune_event_nodes_locked(keep="__head__")
            store.append(instant)
            meta = self._node_event_meta.setdefault("__head__", {})
            meta["pid"] = os.getpid()
            meta["ts"] = time.monotonic()
            meta["received"] = meta.get("received", 0) + 1
        if self._events_ring is not None:
            self._events_ring.append_many(
                [{**instant, "node": "__head__"}])

    def _publish_node_death(self, node_id: str, address: str = ""):
        self._publisher.publish("node_death",
                                {"node_id": node_id,
                                 "address": address})

    def _forget_actors_on(self, node_id: str) -> List[bytes]:
        """Actors on a dead node either enter RESTARTING (spec kept and
        restart budget remaining — reference gcs_actor_manager.h:308)
        or are dropped."""
        dead = [aid for aid, info in self._actors.items()
                if info["node_id"] == node_id and
                info.get("state", "ALIVE") == "ALIVE"]
        gone = []
        for aid in dead:
            info = self._actors.get(aid)
            mr = info.get("max_restarts", 0)
            if (info.get("spec") is not None
                    and (mr < 0  # max_restarts=-1: infinite budget
                         or info.get("restarts_used", 0) < mr)):
                info["state"] = "RESTARTING"
                self._journal({"op": "actor_put", "actor_id": aid,
                               "info": {k: v for k, v in info.items()
                                        if k != "restart_deadline"}})
                self._restart_pending.append(aid)
                self._restart_cond.notify_all()
                self._publisher.publish("actor_state", {
                    "actor_id": aid, "state": "RESTARTING"})
            else:
                self._actors.pop(aid)
                self._journal({"op": "actor_del", "actor_id": aid})
                if info.get("name"):
                    self._named.pop(
                        (info.get("namespace", ""), info["name"]))
                gone.append(aid)
        return gone

    def _restart_loop(self):
        while not self._stop.is_set():
            with self._restart_cond:
                while not self._restart_pending:
                    # Stop check BEFORE the wait: shutdown() can set
                    # _stop and notify between our outer loop check and
                    # acquiring the condition — an untimed wait here
                    # would sleep through that lost notification
                    # forever.  The timeout is belt-and-braces.
                    if self._stop.is_set():
                        return
                    self._restart_cond.wait(timeout=1.0)
                aid = self._restart_pending.pop(0)
                if not self._is_primary or self._deposed:
                    continue  # standby: replicated RESTARTING entries
                    # re-enqueue at promotion, not here
                info = self._actors.get(aid)
                if info is None or info.get("state") != "RESTARTING":
                    continue
                if "restart_deadline" not in info:
                    info["restart_deadline"] = (
                        time.monotonic() + self._restart_timeout)
                spec = info["spec"]
                demand = dict(info.get("resources") or {})
                dead_node = info["node_id"]
                deadline = info["restart_deadline"]
            placed = self._place({"resources": demand,
                                  "exclude": [dead_node]})
            ok = False
            if placed.get("ok"):
                try:
                    # Per-attempt timeout stays well under the overall
                    # restart deadline so one wedged target can't hold
                    # the restart thread for every other actor's budget.
                    resp = self._pool.get(placed["address"]).call(
                        "create_actor", spec, timeout=60.0)
                    ok = bool(resp.get("ok"))
                except Exception:  # raylint: disable=ft-exception-swallow -- any failure (transport or remote create error) routes to the same retry-under-deadline path below
                    ok = False
            kill_leaked = False
            with self._lock:
                info = self._actors.get(aid)
                if info is None:
                    # Killed/removed while we were restarting it: the
                    # fresh replica (if any) must not leak.  The kill
                    # RPC runs AFTER the lock drops — a blocking call
                    # here would wedge every other head handler for up
                    # to its timeout.
                    kill_leaked = ok
                elif ok:
                    info["node_id"] = placed["node_id"]
                    info["address"] = placed["address"]
                    info["restarts_used"] = \
                        info.get("restarts_used", 0) + 1
                    info["state"] = "ALIVE"
                    info.pop("restart_deadline", None)
                    self._journal({"op": "actor_put", "actor_id": aid,
                                   "info": dict(info)})
                    self._publisher.publish("actor_state", {
                        "actor_id": aid, "state": "ALIVE",
                        "node_id": placed["node_id"],
                        "address": placed["address"]})
                elif time.monotonic() < deadline:
                    # Transient placement/RPC failure: keep trying —
                    # the reference GCS reschedules while the restart
                    # budget remains, it doesn't drop on first miss.
                    self._restart_pending.append(aid)
                else:
                    self._actors.pop(aid)
                    self._journal({"op": "actor_del", "actor_id": aid})
                    if info.get("name"):
                        self._named.pop(
                            (info.get("namespace", ""), info["name"]))
            try:
                self._commit_persist()
            except (ConnectionError, TimeoutError, StaleEpochError):  # raylint: disable=ft-exception-swallow -- a deposed/standby-starved barrier must not kill the restart thread; the role gate after the pop takes over next iteration
                continue
            if kill_leaked:
                try:
                    self._pool.get(placed["address"]).call(
                        "kill_actor",
                        {"actor_id": loads(spec)["actor_id"],
                         "no_restart": True}, timeout=10.0)
                except Exception:  # raylint: disable=ft-exception-swallow -- best-effort leak cleanup; an uncaught error here would kill the restart thread for every future actor
                    pass
                continue
            if info is None:
                continue
            if not ok:
                self._stop.wait(self._restart_retry)

    def _list_nodes(self, _p):
        crashes = self._crash_counts()
        with self._lock:
            return [{
                "node_id": e.node_id, "address": e.address,
                "total": dict(e.total), "available": dict(e.available),
                "alive": e.alive, "labels": dict(e.labels),
                "name": e.name,
                "crashes": crashes.get(e.node_id, 0),
            } for e in self._nodes.values()]

    def _reap_loop(self):
        """Lease expiry: a node whose lease ran out (no heartbeat
        renewal for one TTL) is declared dead and its epoch FENCED —
        it can only come back through re-registration, which mints a
        strictly newer epoch."""
        while not self._stop.wait(self._lease_ttl / 4):
            if not self._is_primary or self._deposed:
                continue  # a standby must not reap replicated leases
            now = time.monotonic()
            with self._lock:
                in_grace = (self._replay_grace_until
                            and now <= self._replay_grace_until)
                dead = []
                if not in_grace:
                    for e in self._nodes.values():
                        if e.alive and e.lease_expires < now:
                            e.alive = False
                            self._membership_version += 1
                            self._journal({"op": "node_dead",
                                           "node_id": e.node_id})
                            self._tombstone_locked(e.node_id)
                            self._forget_actors_on(e.node_id)
                            dead.append((e.node_id, e.address))
                if (self._replay_grace_until
                        and now > self._replay_grace_until):
                    # Post-restart sweep: replayed actors whose node
                    # never reattached get the node-death treatment
                    # (restart on a survivor or drop).
                    self._replay_grace_until = 0.0
                    known = set(self._nodes)
                    orphan_nodes = {
                        info["node_id"]
                        for info in self._actors.values()
                        if info["node_id"] not in known
                        and info.get("state", "ALIVE") == "ALIVE"}
                    for nid in orphan_nodes:
                        self._forget_actors_on(nid)
            if dead:
                _lease_metrics()["expirations"].inc(len(dead))
            try:
                self._commit_persist()
            except (ConnectionError, TimeoutError, StaleEpochError):  # raylint: disable=ft-exception-swallow -- a deposed/standby-starved barrier must not kill the reaper thread; the records stay journaled locally and the role gate at the loop top takes over next tick
                continue
            for nid, addr in dead:
                self._publish_node_death(nid, addr)

    # ---------------------------------------------------------- placement
    def _place(self, p):
        """Cluster scheduling policy (reference:
        raylet/scheduling/policy/* — hybrid, spread, node-affinity,
        node-label).  Parameters:

        - ``resources``: the demand.
        - ``strategy``: "default" (max current headroom) or "spread"
          (round-robin over fitting nodes).
        - ``available_only``: only nodes whose CURRENT (heartbeat −
          reservations) availability fits qualify — used by callers
          spilling load off a saturated node, where queueing on a busy
          peer would be worse than queueing locally.
        - ``affinity_node_id`` / ``affinity_soft``: NodeAffinity; hard
          affinity fails if the node is dead or misses the demand.
        - ``label_hard`` / ``label_soft``: NodeLabel filters.
        Placements debit a TTL'd reservation so rapid successive calls
        don't oversubscribe one node between heartbeats."""
        if not self._is_primary or self._deposed:
            # Placement debits reservations and feeds the autoscaler
            # ledger — primary-only state.  (Internal callers — the
            # restart loop — only run on a primary.)
            from ..exceptions import NotPrimaryError

            raise NotPrimaryError(
                "placement on a non-primary head",
                generation=self._generation,
                primary_hint=self._known_primary or "",
                context={"method": "place"})
        demand: Dict[str, float] = p["resources"]
        exclude = set(p.get("exclude", ()))
        strategy = p.get("strategy", "default")
        available_only = p.get("available_only", False)
        affinity = p.get("affinity_node_id")
        with self._lock:
            if affinity is not None:
                e = self._nodes.get(affinity)
                if (e is not None and e.alive
                        and e.node_id not in exclude
                        and all(e.total.get(k, 0) >= v
                                for k, v in demand.items())):
                    e.reserve(demand)
                    return {"ok": True, "node_id": e.node_id,
                            "address": e.address}
                if not p.get("affinity_soft", False):
                    return {"ok": False,
                            "error": f"node affinity target "
                                     f"{str(affinity)[:8]} is dead, "
                                     f"excluded, or cannot fit {demand}"}
                # Soft affinity: fall through to the default choice.
            candidates = [
                e for e in self._nodes.values()
                if e.alive and e.node_id not in exclude
                and all(e.total.get(k, 0) >= v for k, v in demand.items())
            ]
            hard = p.get("label_hard") or {}
            if hard:
                candidates = [
                    e for e in candidates
                    if all(e.labels.get(k) == v for k, v in hard.items())]
            soft = p.get("label_soft") or {}
            if soft:
                preferred = [
                    e for e in candidates
                    if all(e.labels.get(k) == v for k, v in soft.items())]
                if preferred:
                    candidates = preferred
            # One effective-availability snapshot per candidate, shared
            # by the filter and the headroom ranking below.
            avail = {e.node_id: e.effective_available()
                     for e in candidates}
            if available_only:
                candidates = [
                    e for e in candidates
                    if all(avail[e.node_id].get(k, 0) >= v
                           for k, v in demand.items())]
            if not candidates:
                if not available_only:
                    # Demand ledger for the autoscaler (reference:
                    # pending resource demands feeding
                    # resource_demand_scheduler.py): infeasible
                    # placements are the scale-up signal.
                    self._unmet_demands.append(
                        (time.monotonic(), dict(demand)))
                    del self._unmet_demands[:-200]
                return {"ok": False, "available_only": available_only,
                        "error": f"no node can fit {demand} "
                                 f"(available_only={available_only}, "
                                 f"nodes: {[(e.node_id[:8], e.total) for e in self._nodes.values()]})"}

            if strategy == "spread":
                # Round-robin over the fitting nodes in stable order
                # (reference: spread_scheduling_policy).
                candidates.sort(key=lambda e: e.node_id)
                best = candidates[self._spread_rr % len(candidates)]
                self._spread_rr += 1
            else:
                def headroom(e: NodeEntry) -> float:
                    a = avail[e.node_id]
                    return min((a.get(k, 0) - v
                                for k, v in demand.items()), default=0)

                best = max(candidates, key=headroom)
            best.reserve(demand)
        return {"ok": True, "node_id": best.node_id,
                "address": best.address}

    # ----------------------------------------------------------------- kv
    def _kv_put(self, p):
        key = (p.get("ns", ""), p["key"])
        with self._lock:
            exists = self._kv.contains(key)
            if p.get("overwrite", True) or not exists:
                self._kv.put(key, p["value"])
                self._journal({"op": "kv_put", "ns": key[0],
                               "key": key[1], "value": p["value"]})
                return {"ok": True, "added": not exists}
        return {"ok": True, "added": False}

    def _kv_get(self, p):
        # Lock-free read: one shard lock, no contention with mutations.
        key = (p.get("ns", ""), p["key"])
        sentinel = object()
        value = self._kv.get(key, sentinel)
        if value is sentinel:
            return {"found": False, "value": None}
        return {"found": True, "value": value}

    def _kv_del(self, p):
        key = (p.get("ns", ""), p["key"])
        with self._lock:
            deleted = self._kv.pop(key, None) is not None
            if deleted:
                self._journal({"op": "kv_del", "ns": key[0],
                               "key": key[1]})
            return {"deleted": deleted}

    def _kv_keys(self, p):
        prefix = p.get("prefix", "")
        ns = p.get("ns", "")
        return [k for (n, k) in self._kv.keys() if n == ns
                and k.startswith(prefix)]

    # ------------------------------------------------------------- actors
    def _register_actor(self, p):
        with self._lock:
            info = {
                "node_id": p["node_id"], "address": p["address"],
                "name": p.get("name", ""),
                "namespace": p.get("namespace", ""),
                "klass": p.get("klass"),
                # Restart machinery: the pickled creation bundle is
                # replayed on a survivor when this actor's node dies.
                "spec": p.get("spec"),
                "max_restarts": int(p.get("max_restarts", 0)),
                "max_task_retries": int(p.get("max_task_retries", 0)),
                "resources": p.get("resources") or {},
                "restarts_used": 0,
                "state": "ALIVE",
            }
            if p.get("name"):
                key = (p.get("namespace", ""), p["name"])
                existing = self._named.get(key)
                if existing is not None and existing != p["actor_id"]:
                    return {"ok": False,
                            "error": f"actor name {p['name']!r} "
                                     "already taken",
                            "existing": existing}
                self._named.put(key, p["actor_id"])
            self._actors.put(p["actor_id"], info)
            self._journal({"op": "actor_put",
                           "actor_id": p["actor_id"],
                           "info": dict(info)})
        return {"ok": True}

    @staticmethod
    def _actor_view(info):
        # The creation bundle stays head-side; lookups don't ship it.
        return {k: v for k, v in info.items() if k != "spec"}

    def _lookup_actor(self, p):
        # Lock-free read through the sharded store.
        info = self._actors.get(p["actor_id"])
        if info is None:
            return {"found": False}
        return {"found": True, **self._actor_view(info)}

    def _lookup_named_actor(self, p):
        key = (p.get("namespace", ""), p["name"])
        aid = self._named.get(key)
        info = self._actors.get(aid) if aid else None
        if info is None:
            return {"found": False}
        return {"found": True, "actor_id": aid, **self._actor_view(info)}

    def _remove_actor(self, p):
        with self._lock:
            info = self._actors.pop(p["actor_id"], None)
            if info and info.get("name"):
                self._named.pop(
                    (info.get("namespace", ""), info["name"]), None)
            if info is not None:
                self._journal({"op": "actor_del",
                               "actor_id": p["actor_id"]})
        return {"ok": info is not None}

    def _list_actors_rpc(self, p):
        """Optionally server-side filtered (state API: ``ray_tpu list
        actors --node/--state`` applies filters HERE, not client-side
        — the reference state aggregator's predicate pushdown)."""
        node = (p or {}).get("node") if isinstance(p, dict) else None
        state = (p or {}).get("state") if isinstance(p, dict) else None
        # Same normalization as the task path (node_state uppercases):
        # `--state alive` must not silently match zero actors.
        state = state.upper() if isinstance(state, str) else state
        return [{"actor_id": aid, "node_id": i["node_id"],
                 "name": i["name"],
                 "state": i.get("state", "ALIVE")}
                for aid, i in self._actors.items()
                if (node is None
                    or str(i["node_id"]).startswith(node))
                and (state is None
                     or i.get("state", "ALIVE") == state)]

    # ---------------------------------------------------------------- pgs
    def _create_pg(self, p):
        """Assign each bundle a node (PACK: fill one node first;
        SPREAD: round-robin) and debit the head's availability view.
        Reference: two-phase commit against raylets (A.13) — collapsed
        to one phase here since the head's view is authoritative for
        placement and nodes gate locally."""
        bundles: List[Dict[str, float]] = p["bundles"]
        strategy = p.get("strategy", "PACK")
        pg_id = p["pg_id"]
        with self._lock:
            alive = [e for e in self._nodes.values() if e.alive]
            if not alive:
                return {"ok": False, "error": "no alive nodes"}
            if strategy in ("SLICE_PACK", "SLICE_SPREAD"):
                result = self._place_pg_by_slice(bundles, strategy, alive)
                if not result.get("ok"):
                    return result
                assignment = result["nodes"]
                self._pgs.put(pg_id, {"bundles": bundles,
                                      "nodes": assignment})
                self._journal({"op": "pg_put", "pg_id": pg_id,
                               "bundles": bundles,
                               "nodes": assignment})
                addr = {e.node_id: e.address for e in alive}
                return {"ok": True, "nodes": assignment,
                        "addresses": [addr[n] for n in assignment]}
            assignment: List[str] = []
            # Track debits against a scratch copy; commit on success.
            scratch = {e.node_id: dict(e.available) for e in alive}
            order = sorted(alive, key=lambda e: -sum(e.total.values()))
            rr = 0
            for bundle in bundles:
                placed = None
                if strategy in ("PACK", "STRICT_PACK"):
                    pool = order
                else:  # SPREAD / STRICT_SPREAD round-robin
                    pool = order[rr:] + order[:rr]
                    rr = (rr + 1) % len(order)
                for e in pool:
                    avail = scratch[e.node_id]
                    if all(e.total.get(k, 0) >= v
                           for k, v in bundle.items()):
                        if strategy in ("STRICT_SPREAD",) and \
                                e.node_id in assignment:
                            continue
                        for k, v in bundle.items():
                            avail[k] = avail.get(k, 0) - v
                        placed = e.node_id
                        break
                if placed is None:
                    return {"ok": False,
                            "error": f"bundle {bundle} does not fit "
                                     f"any node (strategy={strategy})"}
                assignment.append(placed)
            self._pgs.put(pg_id, {"bundles": bundles,
                                  "nodes": assignment})
            self._journal({"op": "pg_put", "pg_id": pg_id,
                           "bundles": bundles, "nodes": assignment})
            addr = {e.node_id: e.address for e in alive}
        return {"ok": True, "nodes": assignment,
                "addresses": [addr[n] for n in assignment]}

    def _place_pg_by_slice(self, bundles, strategy, alive):
        """ICI-topology-aware bundle placement over slice labels
        (core/tpu_topology.py; reference TPU-pod detection:
        _private/accelerators/tpu.py:14-42).

        - ``SLICE_PACK``: all bundles onto the hosts of ONE slice, in
          worker-index order — a train gang whose collectives must ride
          ICI.  Prefers the smallest slice that fits (leaves big slices
          for big gangs).
        - ``SLICE_SPREAD``: bundle i onto slice i (distinct slices,
          sorted by name) — cross-slice pipeline stages where only
          stage boundaries cross DCN.  Within a slice the lowest
          worker-index host that fits is used.

        A node without a slice label forms its own single-node
        pseudo-slice, so both strategies degrade gracefully on
        unlabeled (CPU-sim / single-host) clusters."""
        from ..core.tpu_topology import SLICE_LABEL, WORKER_INDEX_LABEL

        def widx(e):
            try:
                return int(e.labels.get(WORKER_INDEX_LABEL, ""))
            except ValueError:
                return 1 << 30

        slices: Dict[str, List[NodeEntry]] = {}
        for e in alive:
            key = e.labels.get(SLICE_LABEL) or f"node:{e.node_id}"
            slices.setdefault(key, []).append(e)
        for members in slices.values():
            members.sort(key=lambda e: (widx(e), e.node_id))

        def fit_on(members, wanted):
            """Fit ``wanted`` bundles onto ``members`` in worker-index
            order, one bundle per host round-robin (gang semantics:
            bundle i ↔ slice worker i), falling back to any member with
            capacity; None if infeasible."""
            scratch = {e.node_id: dict(e.available) for e in members}
            out = []
            for i, bundle in enumerate(wanted):
                placed = None
                rotated = members[i % len(members):] + \
                    members[:i % len(members)]
                for e in rotated:
                    if all(scratch[e.node_id].get(k, 0) >= v
                           for k, v in bundle.items()):
                        for k, v in bundle.items():
                            scratch[e.node_id][k] = \
                                scratch[e.node_id].get(k, 0) - v
                        placed = e.node_id
                        break
                if placed is None:
                    return None
                out.append(placed)
            return out

        if strategy == "SLICE_PACK":
            # Smallest adequate slice first; name as tiebreak for
            # determinism.
            for key in sorted(slices, key=lambda k: (len(slices[k]), k)):
                got = fit_on(slices[key], bundles)
                if got is not None:
                    return {"ok": True, "nodes": got}
            return {"ok": False,
                    "error": f"no single slice fits all {len(bundles)} "
                             f"bundles (SLICE_PACK; slices: "
                             f"{sorted(slices)})"}
        # SLICE_SPREAD: one distinct slice per bundle.
        keys = sorted(slices)
        if len(keys) < len(bundles):
            return {"ok": False,
                    "error": f"SLICE_SPREAD needs {len(bundles)} "
                             f"slices, cluster has {len(keys)}"}
        assignment = []
        used = set()
        for bundle in bundles:
            placed = None
            for key in keys:
                if key in used:
                    continue
                got = fit_on(slices[key], [bundle])
                if got is not None:
                    placed = got[0]
                    used.add(key)
                    break
            if placed is None:
                return {"ok": False,
                        "error": f"bundle {bundle} fits no unused "
                                 f"slice (SLICE_SPREAD)"}
            assignment.append(placed)
        return {"ok": True, "nodes": assignment}

    def _remove_pg(self, p):
        with self._lock:
            removed = self._pgs.pop(p["pg_id"], None) is not None
            if removed:
                self._journal({"op": "pg_del", "pg_id": p["pg_id"]})
            return {"ok": removed}

    def shutdown(self):
        self._stop.set()
        with self._restart_cond:
            self._restart_cond.notify_all()
        if self._repl is not None:
            self._repl.stop()
        self._server.shutdown()
        self._pool.close_all()
        self._restarter.join(timeout=2.0)
        self._reaper.join(timeout=2.0)
        if self._alert_thread is not None:
            self._alert_thread.join(timeout=2.0)
        if self._standby_watch is not None:
            self._standby_watch.join(timeout=2.0)
        if self._compactor is not None:
            self._compactor.join(timeout=2.0)
        if self._log is not None:
            self._log.close()
        for ring in (self._events_ring, self._logs_ring,
                     self._metrics_ring):
            if ring is not None:
                ring.close()


def main():  # pragma: no cover - exercised via subprocess in tests
    import argparse
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--storage", default=None,
                    help="durable-table path (journal + snapshot); "
                         "restart at the same port replays state")
    ap.add_argument("--standby-of", default=None,
                    help="primary head address: boot as a hot "
                         "standby tailing its journal (promotes when "
                         "the primary's lease lapses)")
    ap.add_argument("--repl-mode", default=None,
                    choices=("sync", "async"),
                    help="standby ack mode (default: "
                         "RAY_TPU_HEAD_REPL_MODE or sync)")
    args = ap.parse_args()
    head = HeadServer(args.host, args.port, storage_path=args.storage,
                      standby_of=args.standby_of,
                      repl_mode=args.repl_mode)
    print(f"RAY_TPU_HEAD_ADDRESS={head.address}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        sys.exit(0)


if __name__ == "__main__":
    main()
