"""Role assembly + process entry points.

(ref: src/dbnode/server/server.go:160 Run — wire config into storage,
topology, listeners, bootstrap; src/query/server/query.go:172;
aggregator/server/.)  A shared KV store stands in for etcd: pass a
`MemStore` for in-process clusters or a `FileStore` path for
multi-process ones (m3_tpu/cluster/kv.py).
"""

from __future__ import annotations

import argparse
import threading
import time

from m3_tpu import attribution, observe
from m3_tpu.aggregator import Aggregator, FlushManager
from m3_tpu.aggregator.transport import AggregatorIngestServer
from m3_tpu.client.node import DatabaseNode
from m3_tpu.client.tcp import NodeServer
from m3_tpu.cluster.kv import MemStore
from m3_tpu.cluster.service import PlacementService
from m3_tpu.coordinator import Coordinator
from m3_tpu.msg import M3MsgFlushHandler, Producer
from m3_tpu.services.config import (AggregatorConfig, CoordinatorConfig,
                                    DBNodeConfig, load_aggregator_config,
                                    load_coordinator_config,
                                    load_dbnode_config)
from m3_tpu.storage.cluster_node import ClusterStorageNode
from m3_tpu.storage.database import Database, DatabaseOptions
from m3_tpu.storage.namespace import NamespaceOptions, RetentionOptions
from m3_tpu.utils import instrument, tracing


def _apply_attribution(ac) -> None:
    """Wire the workload-attribution config into the process-global
    accountant + exemplar switch (both are process-wide: one metrics
    registry, one accountant per process)."""
    attribution.configure(enabled=ac.enabled,
                          sketch_capacity=ac.sketch_capacity,
                          tenant_cap=ac.tenant_cap)
    instrument.set_exemplars(ac.exemplars)


def _apply_observe(oc) -> None:
    """Bring up the flight recorder (continuous profiler + stall
    watchdog) per config.  Refcounted process-global: an in-process
    coordinator + db node pair shares one recorder, one watchdog, one
    task ledger.  The interpreter's full collections are clocked from
    here on (one ``gc.callbacks`` entry a process)."""
    observe.start(oc)
    tracing.watch_collector()


def _build_self_scraper(ss, db, write_fn, instance: str, role: str):
    """Create the internal-telemetry namespace (own retention, no
    commit log — telemetry must not bloat the WAL) and the scrape
    loop that feeds it (ref: M3 monitoring M3 at Uber)."""
    from m3_tpu.selfscrape import SelfScraper

    if ss.namespace not in db.namespaces():
        db.create_namespace(NamespaceOptions(
            name=ss.namespace,
            retention=RetentionOptions(
                retention_period=ss.retention.retention_period,
                block_size=ss.retention.block_size,
                buffer_past=ss.retention.buffer_past,
                buffer_future=ss.retention.buffer_future),
            writes_to_commit_log=False))
    return SelfScraper(write_fn, namespace=ss.namespace,
                       interval_s=ss.interval / 1e9,
                       instance=instance, role=role,
                       max_pending_batches=ss.max_pending_batches)


class DBNodeService:
    """(ref: dbnode/server/server.go Run)."""

    def __init__(self, cfg: DBNodeConfig, kv_store=None,
                 peer_transports: dict | None = None):
        self.cfg = cfg
        _apply_attribution(cfg.attribution)
        self.db = Database(DatabaseOptions(
            path=cfg.path, num_shards=cfg.num_shards,
            commit_log_enabled=cfg.commit_log_enabled,
            cache=cfg.cache.to_options(),
            index=cfg.index.to_options()))
        for ns in cfg.namespaces:
            ret = ns.get("retention", {})
            self.db.create_namespace(NamespaceOptions(
                name=ns["name"],
                retention=RetentionOptions(**ret) if ret
                else RetentionOptions(),
                writes_to_commit_log=ns.get("writes_to_commit_log",
                                            True),
                cold_writes_enabled=ns.get("cold_writes_enabled", True)))
        res = cfg.resilience
        self.admission = (res.admission.to_controller()
                          if res.admission.enabled else None)
        self._insert_queue = None
        if cfg.insert_queue_enabled:
            from m3_tpu.storage.insert_queue import InsertQueue
            # with admission on, over-watermark writers are rejected
            # (AdmissionRejected -> 429 at the HTTP edge) instead of
            # blocking in the queue
            self._insert_queue = InsertQueue(self.db,
                                             admission=self.admission)
        try:
            self.node = DatabaseNode(self.db, cfg.instance_id,
                                     insert_queue=self._insert_queue)
            self.server = NodeServer(self.node, port=cfg.listen_port)
        except BaseException:
            # the queue starts a drain thread at construction; a later
            # __init__ failure (port in use, ...) must not leak it —
            # stop() can never run on a half-built service
            if self._insert_queue is not None:
                self._insert_queue.close()
            raise
        self.mediator = None
        self.runtime_mgr = None
        if kv_store is not None:
            # hot-reloadable runtime options via KV watch
            from m3_tpu.cluster.runtime import RuntimeOptionsManager
            self.runtime_mgr = RuntimeOptionsManager(kv_store)
            self.runtime_mgr.register(self.db.set_runtime_options)
        self.cluster: ClusterStorageNode | None = None
        if kv_store is not None and cfg.reconciler.enabled:
            self.cluster = ClusterStorageNode(
                self.db, cfg.instance_id,
                PlacementService(kv_store, key="_placement/m3db"),
                peer_transports or {},
                drain=cfg.reconciler.drain)
        self._kv_store = kv_store
        self._advert = None
        # background health probes over the peer transports: dead
        # peers are ejected from this node's routing view with
        # hysteresis (flap dampening), never below quorum eligibility
        self.health_checker = None
        if res.health.enabled and peer_transports:
            from m3_tpu.resilience import HealthChecker
            self.health_checker = HealthChecker(
                peer_transports, **res.health.to_kwargs())
        self.self_scraper = None
        if cfg.self_scrape.enabled:
            # ride the real ingest path: the insert queue when it is
            # on (coalesced, async), else direct database writes
            write_fn = (self._insert_queue.write_batch_async
                        if self._insert_queue is not None
                        else self.db.write_batch)
            self.self_scraper = _build_self_scraper(
                cfg.self_scrape, self.db, write_fn,
                instance=cfg.instance_id, role="dbnode")

    @property
    def endpoint(self) -> str:
        return self.server.endpoint

    def start(self) -> "DBNodeService":
        # Observe refs are taken in start (not __init__) so they pair
        # exactly with the release in stop — a constructor that throws
        # half-built, or a service built but never run, must not leak
        # a refcount that keeps the process-global recorder/watchdog
        # threads alive forever.
        _apply_observe(self.cfg.observe)
        self.db.bootstrap()
        if self.self_scraper is not None:
            self.self_scraper.start()
        if self.health_checker is not None:
            self.health_checker.start()
        self.server.start()
        if self.runtime_mgr is not None:
            self.runtime_mgr.start()
        if self.cluster is not None:
            repair_s = (self.cfg.repair_every / 1e9
                        if self.cfg.repair_every else None)
            self.cluster.start(
                poll_seconds=max(0.05, self.cfg.reconciler.poll / 1e9),
                repair_every_seconds=repair_s)
        if self.cfg.tick_every:
            from m3_tpu.storage.database import Mediator
            self.mediator = Mediator(
                self.db, tick_every=self.cfg.tick_every / 1e9,
                snapshot_every=self.cfg.snapshot_every / 1e9)
            self.mediator.start()
        if self._kv_store is not None:
            # liveness/membership (ref: cluster/services advertise +
            # heartbeat) — operators and peers see this instance live
            from m3_tpu.cluster.services import ServicesRegistry
            self._advert = ServicesRegistry(self._kv_store).advertise(
                "m3db", self.cfg.instance_id, self.endpoint)
        return self

    def prepare_shutdown(self) -> None:
        """Graceful-restart drain (SIGTERM path; ref: dbnode server.go
        deferred shutdown): flip readiness to draining FIRST so the
        health RPC / coordinator ``/health`` answer draining and
        peers' health checkers eject this node, deregister from
        membership, stop the write sources feeding the insert queue,
        drain the queue into the database, then snapshot + drain the
        WAL so the next bootstrap replays only seconds of tail.
        ``stop()`` still runs afterwards for the actual teardown;
        every step here is idempotent against it.  A crash anywhere in
        this sequence loses nothing — acked writes are already in the
        WAL, and the killpoint sweep proves each seam recovers."""
        self.db.begin_drain()
        if self._advert is not None:
            try:
                self._advert.revoke()
            except Exception:  # noqa: BLE001 — a dead control plane
                pass  # must not abort the drain
            self._advert = None
        if self.self_scraper is not None:
            # staleness markers land while the queue still accepts
            self.self_scraper.stop()
            self.self_scraper = None
        if self.mediator is not None:
            # a background snapshot racing prepare_shutdown's own
            # snapshot would just duplicate work; stop it first
            self.mediator.stop()
        if self._insert_queue is not None:
            self._insert_queue.close()  # drains pending into the db
        self.db.prepare_shutdown()

    def stop(self) -> None:
        if self.self_scraper is not None:
            # first: its staleness markers must land before the
            # insert queue drains and the db closes
            self.self_scraper.stop()
        if self._advert is not None:
            try:
                self._advert.revoke()
            except Exception:  # noqa: BLE001 — a dead control plane
                pass  # must not abort the rest of teardown
        if self.health_checker is not None:
            self.health_checker.stop()
        if self.runtime_mgr is not None:
            self.runtime_mgr.stop()
        if self.mediator is not None:
            self.mediator.stop()
        if self.cluster is not None:
            self.cluster.stop()
        self.server.stop()
        if self._insert_queue is not None:
            self._insert_queue.close()  # drains before the db closes
        self.db.close()
        observe.release()


class CoordinatorService:
    """(ref: query/server/query.go Run)."""

    def __init__(self, cfg: CoordinatorConfig, kv_store=None,
                 ruleset=None):
        self.cfg = cfg
        _apply_attribution(cfg.attribution)
        self.db = Database(DatabaseOptions(
            path=cfg.path, num_shards=cfg.num_shards,
            cache=cfg.cache.to_options(),
            index=cfg.index.to_options()))
        self.admission = (cfg.resilience.admission.to_controller()
                          if cfg.resilience.admission.enabled else None)
        # retention ladder: parsed (and thus validated) BEFORE the
        # coordinator builds, so a bad rung spec fails service start
        ladder_cfg = cfg.retention_ladder
        ladder = (ladder_cfg.to_ladder()
                  if ladder_cfg.enabled else None)
        self.coordinator = Coordinator(
            self.db, ruleset=ruleset,
            unagg_namespace=cfg.unagg_namespace,
            agg_namespace=cfg.agg_namespace,
            kv_store=kv_store or MemStore(),
            instance_id=cfg.instance_id,
            http_port=cfg.http_port,
            carbon_port=(None if cfg.carbon_port < 0
                         else cfg.carbon_port),
            admission=self.admission,
            graphite_device=cfg.graphite_device,
            retention_ladder=ladder,
            compaction=ladder_cfg.compaction,
            compaction_hot_window_nanos=ladder_cfg.hot_window,
            compaction_poll_s=ladder_cfg.compaction_poll / 1e9)
        self.self_scraper = None
        if cfg.self_scrape.enabled:
            self.self_scraper = _build_self_scraper(
                cfg.self_scrape, self.db, self.db.write_batch,
                instance=cfg.instance_id, role="coordinator")
        self.rules_engine = None
        if cfg.rules.enabled and cfg.rules.groups:
            from m3_tpu.rules import RulesEngine

            # rules evaluate over (and record back into) the internal
            # telemetry namespace; create it when self-scrape didn't
            if cfg.rules.namespace not in self.db.namespaces():
                ss = cfg.self_scrape
                self.db.create_namespace(NamespaceOptions(
                    name=cfg.rules.namespace,
                    retention=RetentionOptions(
                        retention_period=ss.retention.retention_period,
                        block_size=ss.retention.block_size,
                        buffer_past=ss.retention.buffer_past,
                        buffer_future=ss.retention.buffer_future),
                    writes_to_commit_log=False))
            self.rules_engine = RulesEngine(
                self.db, self.coordinator.store, cfg.rules,
                instance_id=cfg.instance_id,
                write_fn=self.db.write_batch)
            self.coordinator.http.attach_rules_engine(self.rules_engine)
        self.mediator = None

    @property
    def http_port(self) -> int:
        return self.coordinator.http.port

    def start(self) -> "CoordinatorService":
        # Taken here, not in __init__ — see DBNodeService.start.
        _apply_observe(self.cfg.observe)
        # cross-query megabatching: install (or clear) the process
        # scheduler before the HTTP edge starts taking queries
        from m3_tpu import serving
        serving.configure(self.cfg.query_batching)
        self.db.bootstrap()
        if self.self_scraper is not None:
            self.self_scraper.start()
        self.coordinator.start(
            flush_interval_seconds=self.cfg.flush_interval / 1e9)
        if self.rules_engine is not None:
            self.rules_engine.start()
        if self.cfg.tick_every:
            # background tick + periodic snapshot for the embedded db,
            # same as DBNodeService: bounds the WAL replay window of a
            # coordinator crash without a graceful shutdown
            from m3_tpu.storage.database import Mediator
            self.mediator = Mediator(
                self.db, tick_every=self.cfg.tick_every / 1e9,
                snapshot_every=self.cfg.snapshot_interval / 1e9)
            self.mediator.start()
        return self

    def stop(self) -> None:
        if self.mediator is not None:
            # a background snapshot racing teardown's flush/close
            # would duplicate work; stop it first
            self.mediator.stop()
        if self.rules_engine is not None:
            # staleness markers + leases released while the db
            # and KV store still accept writes
            self.rules_engine.stop()
        if self.self_scraper is not None:
            self.self_scraper.stop()  # staleness before the db closes
        self.coordinator.stop()
        self.db.close()
        from m3_tpu import serving
        serving.uninstall()
        observe.release()


class AggregatorService:
    """(ref: aggregator/server: m3msg ingest + elected flush)."""

    def __init__(self, cfg: AggregatorConfig, kv_store):
        from m3_tpu.aggregator.aggregator import AggregatorOptions
        from m3_tpu.aggregator.transport import (ForwardedIngestServer,
                                                 ForwardedWriter)

        self.cfg = cfg
        owned = set(cfg.owned_shards) if cfg.owned_shards else None
        self.forwarded_writer = None
        if self._topic_exists(kv_store, cfg.forwarded_topic):
            self.forwarded_writer = ForwardedWriter(
                kv_store, topic_name=cfg.forwarded_topic)
        self.aggregator = Aggregator(
            AggregatorOptions(num_shards=cfg.num_shards),
            owned_shards=owned,
            forwarded_writer=self.forwarded_writer)
        self.ingest = AggregatorIngestServer(self.aggregator,
                                             port=cfg.listen_port)
        self.forwarded_ingest = None
        if self.forwarded_writer is not None:
            self.forwarded_ingest = ForwardedIngestServer(
                self.aggregator, port=cfg.forwarded_port)
        self.producer = Producer(kv_store, cfg.output_topic)
        self._kv_store = kv_store
        self._advert = None
        from m3_tpu.aggregator.admin import AggregatorAdminServer
        self.admin = AggregatorAdminServer(self, port=cfg.admin_port)
        self.flush_manager = FlushManager(
            self.aggregator, M3MsgFlushHandler(self.producer),
            kv_store, cfg.shard_set_id, cfg.instance_id,
            buffer_past_nanos=cfg.buffer_past,
            election_ttl_seconds=cfg.election_ttl / 1e9)

    @staticmethod
    def _topic_exists(kv_store, name: str) -> bool:
        from m3_tpu.msg import TopicService
        return TopicService(kv_store).exists(name)

    @property
    def endpoint(self) -> str:
        return self.ingest.endpoint

    @property
    def forwarded_endpoint(self) -> str | None:
        return (self.forwarded_ingest.endpoint
                if self.forwarded_ingest is not None else None)

    def start(self) -> "AggregatorService":
        self.ingest.start()
        self.admin.start()
        from m3_tpu.cluster.services import ServicesRegistry
        self._advert = ServicesRegistry(self._kv_store).advertise(
            "m3aggregator", self.cfg.instance_id, self.endpoint)
        if self.forwarded_ingest is not None:
            self.forwarded_ingest.start()
        self.flush_manager.campaign()
        self.flush_manager.open(self.cfg.flush_interval / 1e9)
        return self

    def stop(self) -> None:
        if getattr(self, "_advert", None) is not None:
            try:
                self._advert.revoke()
            except Exception:  # noqa: BLE001 — a dead control plane
                pass  # must not abort the rest of teardown
        self.admin.stop()
        self.flush_manager.close()
        if self.forwarded_writer is not None:
            # drain: the final flush may have produced forwarded writes
            # that are not yet acked by the owning instance
            self.forwarded_writer.close()
        self.producer.close()
        if self.forwarded_ingest is not None:
            self.forwarded_ingest.stop()
        self.ingest.stop()


def _resolve_store(spec: str | None):
    """--kv value -> store: 'host:port' = networked KVClient, anything
    else = DirStore path, None = no control plane."""
    if not spec:
        return None
    host, sep, port = spec.rpartition(":")
    if sep and port.isdigit():
        from m3_tpu.cluster.kv_net import KVClient
        return KVClient(spec)
    from m3_tpu.cluster.kv import DirStore
    return DirStore(spec)


def main(argv=None) -> int:
    """``python -m m3_tpu.services <role> -f config.yml [-f more.yml]``
    (ref: cmd/services mains + x/config/configflag)."""
    ap = argparse.ArgumentParser(prog="m3tpu")
    ap.add_argument("role",
                    choices=["dbnode", "coordinator", "aggregator", "kv"])
    ap.add_argument("-f", dest="configs", action="append", default=[],
                    help="YAML config file (repeatable; later override)")
    ap.add_argument("--kv", default=None,
                    help="control plane: host:port of a kv role process "
                         "(networked, the etcd stand-in) or a local "
                         "directory (DirStore)")
    ap.add_argument("--listen", default="127.0.0.1:0",
                    help="kv role: host:port to serve the KV store on")
    args = ap.parse_args(argv)
    # compiled device programs survive a restart: JAX_COMPILATION_CACHE_DIR
    # places the cache, else <checkout>/.jax_cache
    from m3_tpu.utils import compile_cache
    compile_cache.configure()
    if args.role == "kv":
        from m3_tpu.cluster.kv import DirStore, MemStore
        from m3_tpu.cluster.kv_net import KVServer
        backing = _resolve_store(args.kv) or MemStore()
        if not isinstance(backing, (DirStore, MemStore)):
            raise SystemExit(
                "the kv role SERVES a store; --kv must be a directory "
                "to persist into (or omitted for in-memory), not an "
                "endpoint of another kv")
        host, _, port = args.listen.rpartition(":")
        srv = KVServer(backing, host=host or "127.0.0.1",
                       port=int(port)).start()
        print(f"kv up: {srv.endpoint}", flush=True)
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            srv.stop()
        return 0
    store = _resolve_store(args.kv)
    if args.role == "dbnode":
        svc = DBNodeService(load_dbnode_config(*args.configs),
                            kv_store=store)
    elif args.role == "coordinator":
        svc = CoordinatorService(load_coordinator_config(*args.configs),
                                 kv_store=store)
    else:
        if store is None:
            raise SystemExit("aggregator requires --kv")
        svc = AggregatorService(load_aggregator_config(*args.configs),
                                store)
    svc.start()
    print(f"{args.role} up: "
          f"{getattr(svc, 'endpoint', None) or svc.http_port}",
          flush=True)
    # graceful restart protocol: SIGTERM (the rolling-restart driver's
    # signal, also what process managers send) drains + snapshots via
    # prepare_shutdown before teardown, so the next start bootstraps
    # from the snapshot + a seconds-long WAL tail.  SIGKILL remains the
    # crash path — recovery correctness never depends on this handler.
    stop_ev = threading.Event()
    try:
        import signal
        signal.signal(signal.SIGTERM, lambda s, f: stop_ev.set())
    except (ValueError, OSError):
        pass  # not the main thread (embedded runs): ^C only
    try:
        while not stop_ev.wait(3600):
            pass
    except KeyboardInterrupt:
        pass
    if hasattr(svc, "prepare_shutdown"):
        print(f"{args.role} draining", flush=True)
        try:
            svc.prepare_shutdown()
        except Exception:  # noqa: BLE001 — drain is best-effort;
            pass  # teardown (and crash recovery) must still run
    svc.stop()
    return 0
