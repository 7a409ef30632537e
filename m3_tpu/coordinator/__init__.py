"""Coordinator: ingest + downsampling + query front door.

(ref: src/cmd/services/m3coordinator/ — the coordinator accepts
Prometheus remote write / carbon traffic, writes raw samples to the
unaggregated namespace, matches rollup/mapping rules, feeds an
embedded aggregator, and re-ingests flushed aggregates into
aggregated namespaces; queries fan out across namespaces.)
"""

from __future__ import annotations

import threading

from m3_tpu.aggregator import (Aggregator, FlushManager,
                               StorageFlushHandler)
from m3_tpu.cluster.kv import MemStore
from m3_tpu.coordinator.carbon import CarbonServer
from m3_tpu.coordinator.downsample import (Downsampler,
                                           DownsamplerAndWriter,
                                           prom_samples)
from m3_tpu.metrics.matcher import RuleMatcher, watch_ruleset_updates
from m3_tpu.metrics.rules import RuleSet
from m3_tpu.query.http import CoordinatorServer
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.utils import clock


class Coordinator:
    """Assembles the full coordinator loop over one database:

    remote write / carbon -> DownsamplerAndWriter
        -> raw points into the unaggregated namespace
        -> rule-matched samples into the embedded aggregator
    FlushManager (leader-elected) -> StorageFlushHandler
        -> aggregated points into the aggregated namespace

    (ref: coordinator wiring in src/query/server/query.go:172 Run +
    downsample/options.go newAggregator.)
    """

    def __init__(self, db, ruleset: RuleSet | None = None,
                 unagg_namespace: str = "default",
                 agg_namespace: str = "agg",
                 kv_store: MemStore | None = None,
                 instance_id: str = "coordinator-0",
                 http_port: int = 0, carbon_port: int | None = None,
                 admission=None, retention_ladder=None,
                 compaction: bool = False,
                 compaction_hot_window_nanos: int = 0,
                 compaction_poll_s: float = 30.0,
                 graphite_device: bool | None = None):
        self.db = db
        self.store = kv_store or MemStore()
        if unagg_namespace not in db.namespaces():
            db.create_namespace(NamespaceOptions(name=unagg_namespace))
        if agg_namespace not in db.namespaces():
            # declared aggregated so the query engine's namespace
            # fan-out serves reads from it beyond raw retention
            # (ref: cluster_resolver.go aggregated namespace options)
            db.create_namespace(NamespaceOptions(
                name=agg_namespace, aggregated=True,
                aggregation_resolution=60 * 1_000_000_000))
        # retention ladder (m3_tpu/retention): provision/validate rung
        # namespaces at construction — a rung whose existing namespace
        # declares a different resolution fails HERE, at service start
        self.ladder = retention_ladder
        planner = None
        if retention_ladder is not None:
            from m3_tpu.retention import QueryPlanner
            retention_ladder.provision(db)
            planner = QueryPlanner(retention_ladder, db,
                                   raw_namespace=unagg_namespace)
        self.planner = planner
        self.aggregator = Aggregator()
        # rules live in KV (the R2 store): an explicit ruleset seeds the
        # store; otherwise whatever the store holds applies, and the
        # matcher FOLLOWS the key so admin edits hot-reload
        # (ref: src/metrics/matcher/ ruleset KV watch, src/ctl/service/r2/)
        from m3_tpu.metrics.rules_codec import RuleStore, ruleset_from_dict
        self.rule_store = RuleStore(self.store)
        if ruleset is not None:
            # seed ONLY an empty store: a config ruleset on restart must
            # not destroy rules created through the admin API
            self.rule_store.seed(ruleset)
        self.matcher = RuleMatcher(self.rule_store.get())
        self._rules_stop = threading.Event()
        self._rules_thread = threading.Thread(  # lint: allow-unregistered-thread (target registers "rules_watch" in metrics.matcher)
            target=watch_ruleset_updates,
            args=(self.store, self.rule_store._key, self.matcher,
                  lambda val: ruleset_from_dict(val.json()),
                  self._rules_stop),
            daemon=True)
        self.downsampler = Downsampler(self.matcher, self.aggregator)
        self.writer = DownsamplerAndWriter(db, unagg_namespace,
                                           self.downsampler)
        if retention_ladder is not None:
            # flush output keeps its resolution identity: each sample
            # lands in the rung namespace owning its storage policy's
            # resolution (legacy agg namespace catches the rest)
            from m3_tpu.retention import LadderFlushHandler
            flush_handler = LadderFlushHandler(db, retention_ladder,
                                               agg_namespace)
        else:
            flush_handler = StorageFlushHandler(db, agg_namespace)
        self.flush_manager = FlushManager(
            self.aggregator, flush_handler,
            self.store, "coordinator", instance_id)
        self.http = CoordinatorServer(db, unagg_namespace,
                                      port=http_port,
                                      downsampler_writer=self.writer,
                                      kv_store=self.store,
                                      admission=admission,
                                      planner=planner,
                                      graphite_device=graphite_device)
        self.compactor = None
        if retention_ladder is not None and compaction:
            from m3_tpu.retention import TileCompactionDaemon
            self.compactor = TileCompactionDaemon(
                db, retention_ladder, source_namespace=unagg_namespace,
                kv_store=self.store,
                hot_window_nanos=compaction_hot_window_nanos,
                poll_s=compaction_poll_s)
        self.carbon: CarbonServer | None = None
        if carbon_port is not None:
            try:  # columnar carbon decode (None = no native toolchain)
                from m3_tpu.coordinator.fastpath import CarbonFastPath
                carbon_fp = CarbonFastPath(db, unagg_namespace)
            except Exception:  # noqa: BLE001 - scalar path still serves
                carbon_fp = None
            self.carbon = CarbonServer(self.writer, port=carbon_port,
                                       fastpath=carbon_fp)

    def start(self, flush_interval_seconds: float = 1.0) -> "Coordinator":
        self.flush_manager.campaign()
        self.flush_manager.open(flush_interval_seconds)
        self._rules_thread.start()
        self.http.start()
        if self.compactor is not None:
            self.compactor.start()
        if self.carbon is not None:
            self.carbon.start()
        return self

    def flush_once(self, now_nanos: int | None = None):
        return self.flush_manager.flush_once(
            clock.now_nanos() if now_nanos is None else now_nanos)

    def stop(self) -> None:
        self._rules_stop.set()
        if self._rules_thread.is_alive():
            self._rules_thread.join(timeout=2.0)
        if self.carbon is not None:
            self.carbon.stop()
        if self.compactor is not None:
            self.compactor.close()
        self.http.stop()
        self.flush_manager.close()


__all__ = ["Coordinator", "Downsampler", "DownsamplerAndWriter",
           "CarbonServer", "prom_samples"]
