#!/usr/bin/env bash
# Cold-start a local m3_tpu cluster: kv (etcd stand-in) + dbnode +
# coordinator, each its own process with a pidfile under $M3TPU_RUN.
# The compose-style environment definition the reference ships as
# docker-compose.yml — here plain processes, same topology.
#
# Usage:  deploy/start_cluster.sh [--with-aggregator]
# Ports:  kv 2379 | dbnode 9000 | coordinator HTTP 7201 | carbon 7204
#         (override via M3TPU_KV_PORT / M3TPU_DBNODE_PORT /
#          M3TPU_COORDINATOR_PORT / M3TPU_CARBON_PORT)
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
RUN="${M3TPU_RUN:-/tmp/m3tpu-cluster}"
KV_PORT="${M3TPU_KV_PORT:-2379}"
DB_PORT="${M3TPU_DBNODE_PORT:-9000}"
CO_PORT="${M3TPU_COORDINATOR_PORT:-7201}"
export M3TPU_DBNODE_PORT="$DB_PORT" M3TPU_COORDINATOR_PORT="$CO_PORT"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"
mkdir -p "$RUN"

wait_port() { # host port name timeout_s
  for _ in $(seq 1 $((${4:-30} * 10))); do
    if (exec 3<>"/dev/tcp/$1/$2") 2>/dev/null; then exec 3>&-; return 0; fi
    sleep 0.1
  done
  echo "FATAL: $3 did not open $1:$2" >&2
  "$REPO/deploy/stop_cluster.sh" || true
  exit 1
}

require_free() { # port name — a stale listener would silently serve
                 # this cluster's traffic while the new process dies
  if (exec 3<>"/dev/tcp/127.0.0.1/$1") 2>/dev/null; then
    exec 3>&-
    echo "FATAL: port $1 already in use ($2 from an old run? " \
         "stop it: M3TPU_RUN=<its run dir> deploy/stop_cluster.sh)" >&2
    exit 1
  fi
}

require_free "$KV_PORT" kv
require_free "$DB_PORT" dbnode
require_free "$CO_PORT" coordinator

launch() { # name -- argv...
  local name="$1"; shift
  setsid nohup "$@" >"$RUN/$name.log" 2>&1 &
  echo $! >"$RUN/$name.pid"
  echo "started $name (pid $(cat "$RUN/$name.pid"), log $RUN/$name.log)"
}

# One chip, one process: on an accelerator host only the coordinator
# (the role that runs the device query tier and seals its embedded
# database) may initialise the chip; every other role stays on the CPU
# backend.  JAX_PLATFORMS in the caller's environment still wins for
# the coordinator (e.g. JAX_PLATFORMS=cpu on a host without a chip).
launch kv env JAX_PLATFORMS=cpu python -m m3_tpu.services kv \
  --kv "$RUN/kv-data" --listen "127.0.0.1:$KV_PORT"
wait_port 127.0.0.1 "$KV_PORT" kv

M3TPU_DATA="$RUN/dbnode" launch dbnode env JAX_PLATFORMS=cpu \
  python -m m3_tpu.services dbnode \
  -f "$REPO/deploy/config/dbnode.yml" --kv "127.0.0.1:$KV_PORT"
wait_port 127.0.0.1 "$DB_PORT" dbnode

M3TPU_DATA="$RUN/coordinator" launch coordinator \
  python -m m3_tpu.services coordinator \
  -f "$REPO/deploy/config/coordinator.yml" --kv "127.0.0.1:$KV_PORT"
wait_port 127.0.0.1 "$CO_PORT" coordinator

if [ "${1:-}" = "--with-aggregator" ]; then
  # the aggregator consumes the m3msg ingest topic — create it first
  # through the coordinator's topic-admin API (ref: /api/v1/topic)
  curl -fsS -X POST "http://127.0.0.1:$CO_PORT/api/v1/topic/init" \
    -d '{"name": "aggregator_ingest", "numberOfShards": 64}' >/dev/null
  curl -fsS -X POST "http://127.0.0.1:$CO_PORT/api/v1/topic/init" \
    -d '{"name": "aggregated_metrics", "numberOfShards": 64}' >/dev/null
  launch aggregator env JAX_PLATFORMS=cpu \
    python -m m3_tpu.services aggregator \
    -f "$REPO/deploy/config/aggregator.yml" --kv "127.0.0.1:$KV_PORT"
  wait_port 127.0.0.1 "${M3TPU_AGG_ADMIN_PORT:-6002}" aggregator-admin
fi

echo
echo "cluster up:"
echo "  kv           127.0.0.1:$KV_PORT   (etcd stand-in, DirStore-backed)"
echo "  dbnode       127.0.0.1:$DB_PORT   (node RPC)"
echo "  coordinator  http://127.0.0.1:$CO_PORT  (remote write/query/admin)"
echo "  carbon       127.0.0.1:${M3TPU_CARBON_PORT:-7204}  (graphite line protocol)"
echo "try:  curl 'http://127.0.0.1:$CO_PORT/health'"
