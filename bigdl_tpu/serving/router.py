"""Multi-replica serving tier: a thin HTTP front over N supervised
engine replicas.

PR 5 made a single engine survive bad requests (quarantine, deadlines,
drain); this module moves one failure domain up and makes the SERVICE
survive a bad engine *process*. The reference's L6 serving layer (vLLM
behind FastChat workers) leaves replication and failover to an external
orchestrator; for a TPU-native stack serving heavy traffic we build
that tier in-tree, on the drain/health/flight-recorder substrate the
engine already provides:

- **Replica supervisor** — spawns N ``api_server`` subprocesses, probes
  ``/health`` every ``$BIGDL_TPU_ROUTER_HEALTH_SEC``, restarts crashed
  replicas with exponential backoff, SIGKILLs hung ones (a live process
  whose ``/health`` stops answering — or answers "wedged" off the
  engine's step-loop heartbeat), and quarantines a replica that flaps
  past ``$BIGDL_TPU_ROUTER_CRASH_BUDGET`` deaths inside the crash
  window (the replica-granularity mirror of PR 5's per-request blame).
- **Write-ahead request journal** — every admitted request is recorded
  (raw body: prompt + sampling params, plus the assigned replica)
  BEFORE the first byte is forwarded. When a replica dies mid-flight
  its non-streaming requests are transparently REPLAYED on a healthy
  replica (byte-identical for greedy sampling, since replicas share
  weights); streaming requests get a structured SSE error event with a
  ``retry_after`` hint instead of a dropped socket.
- **Per-replica circuit breakers** — consecutive transport failures
  trip the breaker (routing skips the replica), a cooldown later it
  half-opens (one trial request), success closes it. Plus one optional
  HEDGED retry (``$BIGDL_TPU_ROUTER_HEDGE_MS``): a non-streaming
  request with no response past the hedge latency fires one duplicate
  on a second replica and the first answer wins — the loser's
  connection close triggers the engine's client-disconnect abort, so
  the wasted work frees its slot immediately.
- **Rolling restart** — ``POST /v1/admin/rolling_restart`` drains
  replicas one at a time through PR 5's SIGTERM drain (in-flight work
  finishes, new work is re-routed; a request that races the drain gets
  the replica's 503 and is transparently re-routed), then respawns and
  waits healthy before moving on: a config/weight rollout drops zero
  requests and serves zero 5xx.
- **Prefix-affinity routing** — consistent hash over the prompt prefix
  so shared-system-prompt traffic lands where its prefix-cache entry
  already lives, falling back to least-loaded (live ``/v1/stats``
  occupancy) when the affinity target is down, tripped, or full.

Observability: ``bigdl_tpu_router_*`` metric families (per-replica
state gauge, failover/replay/hedge/breaker-trip/restart counters,
routed-request latency histogram), router events in a flight recorder,
and ``GET /v1/router/stats`` — the JSON snapshot bench embeds. Every
admitted completion gets a W3C-style ``traceparent`` (generated here or
accepted from the client; observability/disttrace.py) forwarded on each
replica hop; ``GET /v1/trace/{trace_id}`` returns the stitched
clock-skew-adjusted fleet timeline and ``GET /v1/traces`` lists recent
slow traces.

Run: ``python -m bigdl_tpu.serving.router --model PATH --replicas 2``
(or ``--tiny-random`` for the checkpoint-free chaos/bench mode).
"""

from __future__ import annotations

import base64
import collections
import dataclasses
import hashlib
import http.client
import itertools
import json
import os
import queue
import socket
import subprocess
import sys
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple

from bigdl_tpu.observability.disttrace import (SpanRecorder,
                                               make_traceparent,
                                               merge_timeline,
                                               new_span_id, new_trace_id,
                                               parse_traceparent,
                                               trace_sampled)
from bigdl_tpu.observability.flight import FlightRecorder
from bigdl_tpu.observability.metrics import MetricsRegistry
from bigdl_tpu.robustness.faults import FaultInjector

ROUTER_HEALTH_ENV = "BIGDL_TPU_ROUTER_HEALTH_SEC"
ROUTER_REPLICAS_ENV = "BIGDL_TPU_ROUTER_REPLICAS"
ROUTER_HEDGE_ENV = "BIGDL_TPU_ROUTER_HEDGE_MS"
ROUTER_CRASH_BUDGET_ENV = "BIGDL_TPU_ROUTER_CRASH_BUDGET"
ROUTER_JOURNAL_ENV = "BIGDL_TPU_ROUTER_JOURNAL"

# replica lifecycle states -> bigdl_tpu_router_replica_state gauge codes
STARTING = "starting"
HEALTHY = "healthy"
UNHEALTHY = "unhealthy"
DRAINING = "draining"
BACKOFF = "backoff"
QUARANTINED = "quarantined"
RETIRED = "retired"                    # scaled down; never respawned
STATE_CODES = {STARTING: 0, HEALTHY: 1, UNHEALTHY: 2, DRAINING: 3,
               BACKOFF: 4, QUARANTINED: 5, RETIRED: 6}

#: fleet roles (api_server.REPLICA_ROLES): decode replicas are reserved
#: for KV-handoff decode work and only take client traffic when nothing
#: else is routable
ROLES = ("mixed", "prefill", "decode")


def resolve_router_health_sec(value: Optional[str] = None) -> float:
    """Health-probe interval in seconds (default 1.0). Raises
    ``ValueError`` on a non-positive or non-numeric value — env_check
    surfaces it; the router falls back to the default."""
    raw = value if value is not None else os.environ.get(
        ROUTER_HEALTH_ENV, "")
    if not raw:
        return 1.0
    sec = float(raw)                   # ValueError propagates
    if sec <= 0:
        raise ValueError(
            f"{ROUTER_HEALTH_ENV} must be positive, got {raw!r}")
    return sec


def resolve_router_replicas(value: Optional[str] = None) -> int:
    """Replica count (default 2, must be >= 1)."""
    raw = value if value is not None else os.environ.get(
        ROUTER_REPLICAS_ENV, "")
    if not raw:
        return 2
    n = int(raw)                       # ValueError propagates
    if n < 1:
        raise ValueError(
            f"{ROUTER_REPLICAS_ENV} must be >= 1, got {raw!r}")
    return n


def resolve_router_hedge_ms(value: Optional[str] = None) -> float:
    """Hedged-retry latency threshold in ms (default 0 = hedging off)."""
    raw = value if value is not None else os.environ.get(
        ROUTER_HEDGE_ENV, "")
    if not raw:
        return 0.0
    ms = float(raw)                    # ValueError propagates
    if ms < 0:
        raise ValueError(
            f"{ROUTER_HEDGE_ENV} must be >= 0 (0 disables), got {raw!r}")
    return ms


def resolve_router_canary_sec(value: Optional[str] = None) -> float:
    """Canary-probe sweep interval (default 0 = canaries off);
    delegates to serving/canary.resolve_canary_sec."""
    from bigdl_tpu.serving.canary import resolve_canary_sec
    return resolve_canary_sec(value)


def resolve_router_journal(value: Optional[str] = None) -> Optional[str]:
    """Durable request-journal path (default None = in-memory only).
    Must be absolute: a relative path silently journals into whatever
    cwd the supervisor happened to start from, which is exactly where
    a crash-recovery replay would then fail to find it."""
    raw = value if value is not None else os.environ.get(
        ROUTER_JOURNAL_ENV, "")
    if not raw:
        return None
    if not os.path.isabs(raw):
        raise ValueError(
            f"{ROUTER_JOURNAL_ENV} must be an absolute path, "
            f"got {raw!r}")
    return raw


def resolve_router_crash_budget(value: Optional[str] = None) -> int:
    """Deaths inside the crash window before a replica is quarantined
    (default 3, must be >= 1)."""
    raw = value if value is not None else os.environ.get(
        ROUTER_CRASH_BUDGET_ENV, "")
    if not raw:
        return 3
    n = int(raw)                       # ValueError propagates
    if n < 1:
        raise ValueError(
            f"{ROUTER_CRASH_BUDGET_ENV} must be >= 1, got {raw!r}")
    return n


@dataclasses.dataclass
class RouterConfig:
    """Knobs for the serving tier. ``None`` fields defer to their env
    variables (resolver fallbacks apply on bad values via env_check)."""
    replicas: Optional[int] = None          # $BIGDL_TPU_ROUTER_REPLICAS
    health_sec: Optional[float] = None      # $BIGDL_TPU_ROUTER_HEALTH_SEC
    hedge_ms: Optional[float] = None        # $BIGDL_TPU_ROUTER_HEDGE_MS
    crash_budget: Optional[int] = None      # $BIGDL_TPU_ROUTER_CRASH_BUDGET
    canary_sec: Optional[float] = None      # $BIGDL_TPU_CANARY_SEC
    health_timeout_sec: float = 2.0    # per-probe HTTP timeout
    unhealthy_after: int = 3           # probe failures before hang-kill
    crash_window_sec: float = 60.0     # deaths inside count to the budget
    backoff_base_sec: float = 0.25     # restart backoff: base * 2^deaths
    backoff_max_sec: float = 30.0
    breaker_threshold: int = 3         # consecutive failures to trip
    breaker_cooldown_sec: float = 2.0  # open -> half-open delay
    affinity_tokens: int = 32          # prompt prefix hashed for affinity
    max_replays: int = 2               # failover replays per request
    connect_timeout_sec: float = 5.0
    forward_timeout_sec: float = 600.0  # backstop; deaths close the socket
    spawn_timeout_sec: float = 180.0   # replica boot -> healthy
    drain_exit_timeout_sec: float = 60.0  # SIGTERM -> exit before SIGKILL
    # how long a request WAITS for a routable replica before 503ing:
    # losing the last healthy replica usually means its replacement is
    # seconds away (backoff + respawn), and giving up instantly would
    # drop exactly the requests the replay journal exists to save
    no_replica_wait_sec: float = 30.0
    # per-index fleet roles ("prefill" / "decode" / "mixed"); shorter
    # than the replica count -> the rest default to "mixed". A prefill
    # replica gets X-Handoff-Targets on its non-streaming forwards and
    # ships KV to a decode replica (api_server /v1/internal/kv_handoff)
    roles: Optional[List[str]] = None
    # decode targets named per handoff (ordered least-loaded)
    handoff_fanout: int = 3
    # durable JSONL journal path (None defers to
    # $BIGDL_TPU_ROUTER_JOURNAL; unset env = in-memory only)
    journal_path: Optional[str] = None
    # POST /v1/admin/migrate_out budget per drained replica: covers
    # exporting + shipping every in-flight sequence, so it scales with
    # max_batch, not one request
    migrate_admin_timeout_sec: float = 30.0
    # brownout level-3 relief: how often one overloaded replica may be
    # asked to push batch-QoS sequences to an idle peer, and how many
    # sequences per nudge
    brownout_migrate_interval_sec: float = 5.0
    brownout_migrate_batch: int = 2

    def resolve(self) -> "RouterConfig":
        out = dataclasses.replace(self)
        if out.replicas is None:
            try:
                out.replicas = resolve_router_replicas()
            except ValueError:
                out.replicas = 2          # env_check reports it
        if out.health_sec is None:
            try:
                out.health_sec = resolve_router_health_sec()
            except ValueError:
                out.health_sec = 1.0
        if out.hedge_ms is None:
            try:
                out.hedge_ms = resolve_router_hedge_ms()
            except ValueError:
                out.hedge_ms = 0.0
        if out.crash_budget is None:
            try:
                out.crash_budget = resolve_router_crash_budget()
            except ValueError:
                out.crash_budget = 3
        if out.canary_sec is None:
            try:
                out.canary_sec = resolve_router_canary_sec()
            except ValueError:
                out.canary_sec = 0.0      # env_check reports it
        if out.journal_path is None:
            try:
                out.journal_path = resolve_router_journal()
            except ValueError:
                out.journal_path = None   # env_check reports it
        return out


class ReplicaLost(RuntimeError):
    """The replica's connection failed mid-request (death, hang-kill,
    connection refused). The failover/replay path catches this."""


class NoReplica(RuntimeError):
    """No routable replica (all down, draining, or breaker-open)."""


@dataclasses.dataclass
class JournalEntry:
    """One admitted request in the write-ahead journal: everything
    needed to replay it on another replica (the raw JSON body IS the
    prompt + SamplingParams), plus failover bookkeeping."""
    rid: str
    path: str
    body: bytes
    stream: bool
    key: int                           # affinity hash
    replica: Optional[int] = None      # currently assigned replica idx
    generation: int = 0                # that replica's spawn generation
    replays: int = 0
    hedged: bool = False
    admitted_at: float = dataclasses.field(default_factory=time.monotonic)
    tenant: Optional[str] = None       # X-Tenant-Id to forward
    # distributed-trace context (observability/disttrace.py):
    # (trace_id, client_parent_span_id or None, router_span_id) — None
    # when the trace was tail-sampled out, so no header is forwarded
    trace: Optional[Tuple[str, Optional[str], str]] = None
    # last observed live-migration hop ({"resume_id", "target"}) — a
    # recovered journal uses it to tell "crashed mid-migration" (fall
    # back to byte-identical replay of the original body) from a plain
    # in-flight request
    migrated: Optional[dict] = None


class RequestJournal:
    """Write-ahead journal of in-flight requests. `admit` happens
    BEFORE the first forward; `complete` removes the entry once the
    client has its answer (or its structured error).

    With ``path`` set every mutation is also appended to a durable
    JSONL file (one fsync-free ``write+flush`` per record — the
    trailing record of a kill -9 may be TORN, which recovery detects
    and skips). Startup recovery replays the complete records:
    admitted-but-never-completed entries come back as
    :attr:`recovered` (their raw bodies replayable byte-identically
    for greedy/seeded sampling), torn or garbled lines are counted in
    :attr:`torn_records`, never trusted. A record only counts as
    committed once its terminating newline hit the file."""

    def __init__(self, path: Optional[str] = None):
        self._entries: Dict[str, JournalEntry] = {}
        self._lock = threading.Lock()
        self.path = path
        self._fh = None
        self.torn_records = 0
        self.recovered: List[JournalEntry] = []
        if path:
            self.recovered, self.torn_records = self._recover(path)
            # truncate after recovery: the recovered entries are the
            # router's to replay; carrying dead records forward would
            # re-recover them after every restart
            self._fh = open(path, "wb")
            for e in self.recovered:
                self._append({
                    "op": "admit", "rid": e.rid, "path": e.path,
                    "body": base64.b64encode(e.body).decode("ascii"),
                    "stream": e.stream, "key": e.key,
                    "tenant": e.tenant, "recovered": True})

    @staticmethod
    def _recover(path: str) -> Tuple[List[JournalEntry], int]:
        try:
            with open(path, "rb") as fh:
                data = fh.read()
        except FileNotFoundError:
            return [], 0
        if not data:
            return [], 0
        lines = data.split(b"\n")
        tail = lines.pop()              # b"" when the file ends clean
        torn = 1 if tail.strip() else 0  # kill -9 mid-append
        live: Dict[str, JournalEntry] = {}
        for line in lines:
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
                op = rec["op"]
                rid = str(rec["rid"])
            except (ValueError, KeyError, TypeError):
                torn += 1               # mid-file garbage: skip, count
                continue
            if op == "admit":
                try:
                    body = base64.b64decode(rec.get("body") or "")
                except (ValueError, TypeError):
                    torn += 1
                    continue
                live[rid] = JournalEntry(
                    rid=rid, path=str(rec.get("path") or
                                      "/v1/completions"),
                    body=body, stream=bool(rec.get("stream")),
                    key=int(rec.get("key") or 0),
                    tenant=rec.get("tenant"))
            elif op == "complete":
                live.pop(rid, None)
            elif op == "migrate":
                e = live.get(rid)
                if e is not None:
                    e.migrated = {"resume_id": rec.get("resume_id"),
                                  "target": rec.get("target")}
        return list(live.values()), torn

    def _append(self, rec: dict) -> None:
        """One JSONL record; caller holds (or IS inside) _lock. The
        newline is the commit marker — a torn write is detected by its
        absence (or the half-written JSON in front of it)."""
        # audited: every caller holds _lock (see docstring), so this
        # read cannot race the locked writers the checker found
        fh = self._fh  # graftlint: disable=lock-guarded-unlocked
        if fh is None:
            return
        try:
            fh.write(json.dumps(rec).encode() + b"\n")
            fh.flush()
        except (OSError, ValueError):
            pass                         # journal loss never 500s traffic

    def admit(self, entry: JournalEntry) -> None:
        with self._lock:
            self._entries[entry.rid] = entry
            self._append({
                "op": "admit", "rid": entry.rid, "path": entry.path,
                "body": base64.b64encode(entry.body).decode("ascii"),
                "stream": entry.stream, "key": entry.key,
                "tenant": entry.tenant})

    def assign(self, rid: str, replica: int, generation: int) -> None:
        with self._lock:
            e = self._entries.get(rid)
            if e is not None:
                e.replica = replica
                e.generation = generation

    def record_migration(self, rid: str, resume_id: Optional[str],
                         target: Optional[str]) -> None:
        """The request's sequence moved mid-decode: journal the hop
        BEFORE the continuation forward, so a router crash between
        commit and continuation recovers to 'replay the original
        body' (slower, byte-identical) instead of a lost request."""
        with self._lock:
            e = self._entries.get(rid)
            if e is not None:
                e.migrated = {"resume_id": resume_id, "target": target}
            self._append({"op": "migrate", "rid": rid,
                          "resume_id": resume_id, "target": target})

    def complete(self, rid: str) -> None:
        with self._lock:
            self._entries.pop(rid, None)
            self._append({"op": "complete", "rid": rid})

    def depth(self) -> int:
        with self._lock:
            return len(self._entries)

    def inflight_on(self, replica: int) -> List[JournalEntry]:
        with self._lock:
            return [e for e in self._entries.values()
                    if e.replica == replica]

    def snapshot(self) -> dict:
        return {"path": self.path, "depth": self.depth(),
                "torn_records": self.torn_records,
                "recovered": len(self.recovered)}

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None


class Replica:
    """Supervisor-side view of one engine replica process."""

    def __init__(self, idx: int, port: int, role: str = "mixed"):
        self.idx = idx
        self.port = port
        self.role = role                 # mixed | prefill | decode
        self.proc: Any = None            # Popen-like handle
        self.state = STARTING
        self.generation = 0              # bumped per (re)spawn
        self.started_at = 0.0
        self.probe_failures = 0
        self.restarts = 0                # lifetime respawns
        self.deaths: collections.deque = collections.deque(maxlen=32)
        self.backoff_until = 0.0
        self.last_exit: Optional[str] = None
        self.planned_restart = False     # rolling restart owns the proc
        self.inflight: set = set()       # router-assigned request ids
        self.occupancy = 0.0             # active/total slots (probed)
        self.queue_depth = 0
        self.brownout = 0                # engine brownout level (probed)
        self.tenants: dict = {}          # per-tenant counters (probed)
        # autoscaler load signals (probed from /v1/stats)
        self.tpot_ewma_ms = 0.0          # decode-step latency EWMA
        self.headroom_frac: Optional[float] = None  # HBM ledger headroom
        # last probed handoff counter block + the spawn generation it
        # belongs to (a respawn resets the replica's counters to zero)
        self.handoff: dict = {}
        self.handoff_gen = -1
        # live-migration counter block probed from /v1/stats
        # ("migration" + summed "wire_rejects"), same per-generation
        # delta discipline as handoff
        self.migration: Optional[dict] = None
        self.migration_counts: dict = {}
        self.migration_gen = -1
        # last brownout level-3 migrate nudge (rate limit)
        self.last_brownout_migrate = 0.0
        # compact live-perf block (roofline util, sentinel state)
        # probed from /v1/stats; feeds the router perf aggregate
        self.perf: Optional[dict] = None
        # compact live-quality block (token NLL, probe NLL,
        # QualitySentinel state) probed from /v1/stats; feeds the
        # router's fleet quality aggregate
        self.quality: Optional[dict] = None
        # compact SLO block (active alerts, worst burn rate) probed
        # from /v1/stats; feeds the router's fleet SLO aggregate
        self.slo: Optional[dict] = None
        # circuit breaker
        self.breaker = "closed"          # closed | open | half_open
        self.breaker_failures = 0
        self.breaker_open_until = 0.0

    @property
    def pid(self) -> Optional[int]:
        return getattr(self.proc, "pid", None)

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def snapshot(self) -> dict:
        return {
            "idx": self.idx, "port": self.port, "pid": self.pid,
            "state": self.state, "role": self.role,
            "generation": self.generation,
            "restarts": self.restarts, "last_exit": self.last_exit,
            "probe_failures": self.probe_failures,
            "breaker": self.breaker,
            "breaker_failures": self.breaker_failures,
            "inflight": len(self.inflight),
            "occupancy": self.occupancy,
            "queue_depth": self.queue_depth,
            "brownout": self.brownout,
            "tpot_ewma_ms": self.tpot_ewma_ms,
            "headroom_frac": self.headroom_frac,
            "handoff": dict(self.handoff),
            "migration": (dict(self.migration)
                          if self.migration else None),
            "perf": dict(self.perf) if self.perf else None,
            "slo": dict(self.slo) if self.slo else None,
            "quality": dict(self.quality) if self.quality else None,
        }


def _retry_after_headers(data: bytes) -> tuple:
    """Rebuild the Retry-After header from a buffered shed/drain
    response body (the replica's header was consumed with the
    buffered read; its JSON error block carries the same value)."""
    try:
        ra = json.loads(data).get("error", {}).get("retry_after")
        if ra:
            return (("Retry-After", str(int(ra))),)
    except (ValueError, AttributeError, TypeError):
        pass
    return ()


def _free_port(host: str = "127.0.0.1") -> int:
    s = socket.socket()
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Router:
    """Supervises N replicas and routes OpenAI-API traffic to them.

    ``replica_cmd`` is the subprocess argv with a ``{port}`` placeholder
    (default: ``api_server`` with the flags the CLI assembled); tests
    inject ``spawn(idx, port) -> Popen-like`` to control the processes
    entirely."""

    def __init__(self, replica_cmd: Optional[List[str]] = None,
                 spawn: Optional[Callable[[int, int], Any]] = None,
                 config: Optional[RouterConfig] = None,
                 ports: Optional[List[int]] = None,
                 host: str = "127.0.0.1",
                 registry: Optional[MetricsRegistry] = None,
                 flight: Optional[FlightRecorder] = None,
                 spawn_env: Optional[Dict[str, str]] = None):
        if replica_cmd is None and spawn is None:
            raise ValueError("pass replica_cmd (argv with a {port} "
                             "placeholder) or a spawn(idx, port) factory")
        self.cfg = (config or RouterConfig()).resolve()
        self.host = host
        self._replica_cmd = replica_cmd
        self._spawn_fn = spawn
        self._spawn_env = spawn_env
        ports = list(ports) if ports else [
            _free_port(host) for _ in range(self.cfg.replicas)]
        if len(ports) != self.cfg.replicas:
            raise ValueError(f"got {len(ports)} ports for "
                             f"{self.cfg.replicas} replicas")
        roles = list(self.cfg.roles or [])
        for ro in roles:
            if ro not in ROLES:
                raise ValueError(f"unknown replica role {ro!r} "
                                 f"(choices: {', '.join(ROLES)})")
        self.replicas = [
            Replica(i, p, role=(roles[i] if i < len(roles) else "mixed"))
            for i, p in enumerate(ports)]
        self.journal = RequestJournal(self.cfg.journal_path)
        # chaos for the router's OWN fleet-internal HTTP clients
        # (net_latency@point= / net_drop@point=, robustness/faults.py);
        # off unless $BIGDL_TPU_FAULTS carries a scoped clause
        self.faults = FaultInjector.from_env()
        self._fault_step = itertools.count(1)
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.flight = flight if flight is not None else FlightRecorder()
        # one traceparent per admitted request (generated here, or
        # accepted from the client) stitches router + replica spans
        # into the GET /v1/trace/{id} timeline
        self.spans = SpanRecorder(service="router")
        self._lock = threading.Lock()
        self._stop = False
        self._wake = threading.Event()
        self._supervisor: Optional[threading.Thread] = None
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._admin_lock = threading.Lock()
        self._rolling = False
        # attached by Autoscaler(router); stats_snapshot embeds its
        # decision log when present
        self.autoscaler: Any = None

        # plain counters mirror the metric families so bench JSON and
        # stats_snapshot() embed them without a registry scrape.
        # Incremented from the supervisor thread AND HTTP handler
        # threads: Counter's += is a read-modify-write, so every
        # touch goes through _count()/counts_snapshot() under _lock
        self.counts = collections.Counter()
        self._g_state = self.registry.gauge(
            "bigdl_tpu_router_replica_state",
            "replica lifecycle state (0 starting, 1 healthy, 2 "
            "unhealthy, 3 draining, 4 backoff, 5 quarantined)",
            ["replica"])
        self._c_failovers = self.registry.counter(
            "bigdl_tpu_router_failovers_total",
            "in-flight requests whose replica died under them")
        self._c_replays = self.registry.counter(
            "bigdl_tpu_router_replays_total",
            "non-streaming requests replayed on another replica")
        self._c_hedges = self.registry.counter(
            "bigdl_tpu_router_hedges_total",
            "hedged duplicate requests fired past the latency threshold")
        self._c_trips = self.registry.counter(
            "bigdl_tpu_router_breaker_trips_total",
            "circuit-breaker open transitions", ["replica"])
        self._c_restarts = self.registry.counter(
            "bigdl_tpu_router_restarts_total",
            "replica respawns (crash recovery + rolling restarts)",
            ["replica"])
        self._c_requests = self.registry.counter(
            "bigdl_tpu_router_requests_total",
            "routed requests by replica and response code",
            ["replica", "code"])
        self._h_latency = self.registry.histogram(
            "bigdl_tpu_router_request_seconds",
            "end-to-end routed request latency")
        self._c_canary_probes = self.registry.counter(
            "bigdl_tpu_router_canary_probes_total",
            "golden-canary correctness probes sent to replicas")
        self._c_canary_fail = self.registry.counter(
            "bigdl_tpu_router_canary_failures_total",
            "canary byte mismatches (each quarantines its replica)",
            ["replica"])

        # golden-canary prober (serving/canary.py): periodic greedy
        # probes through each healthy replica; byte mismatch vs the
        # recorded golden quarantines the replica via canary_mismatch.
        # Off unless canary_sec > 0 ($BIGDL_TPU_CANARY_SEC).
        from bigdl_tpu.serving.canary import CanaryProber
        self.canary = CanaryProber(self, self.cfg.canary_sec or 0.0)

        # journal recovery surfaces its findings once, at boot: torn
        # trailing records (kill -9 mid-append) are counted and
        # skipped, complete-but-unfinished admits come back for replay
        if self.journal.torn_records:
            self._count("journal_torn_records",
                        self.journal.torn_records)
            self.flight.record("journal_torn",
                               records=self.journal.torn_records,
                               path=self.journal.path)
        if self.journal.recovered:
            self._count("journal_recovered",
                        len(self.journal.recovered))
            self.flight.record(
                "journal_recovered",
                entries=len(self.journal.recovered),
                migrated_inflight=sum(
                    1 for e in self.journal.recovered
                    if e.migrated is not None),
                path=self.journal.path)

    # -- lifecycle ----------------------------------------------------------

    def start(self, wait_healthy: bool = True) -> None:
        for r in self.replicas:
            self._respawn(r, initial=True)
        self._supervisor = threading.Thread(target=self._supervise,
                                            daemon=True)
        self._supervisor.start()
        self.canary.start()
        if wait_healthy:
            deadline = time.monotonic() + self.cfg.spawn_timeout_sec
            while time.monotonic() < deadline:
                if any(r.state == HEALTHY for r in self.replicas):
                    return
                time.sleep(0.05)
            raise RuntimeError(
                "no replica became healthy within "
                f"{self.cfg.spawn_timeout_sec:.0f}s; last exits: "
                f"{[r.last_exit for r in self.replicas]}")

    def shutdown(self) -> None:
        self._stop = True
        self.canary.stop()
        self._wake.set()
        if self._httpd is not None:
            self._httpd.shutdown()
        if self._supervisor is not None:
            self._supervisor.join(timeout=5)
        for r in self.replicas:
            if r.proc is None:
                continue
            try:
                r.proc.terminate()
            except Exception:
                pass
        deadline = time.monotonic() + 5.0
        for r in self.replicas:
            if r.proc is None:
                continue
            while r.proc.poll() is None and time.monotonic() < deadline:
                time.sleep(0.05)
            if r.proc.poll() is None:
                try:
                    r.proc.kill()
                except Exception:
                    pass
        self.journal.close()

    def _spawn(self, idx: int, port: int, role: str = "mixed"):
        if self._spawn_fn is not None:
            return self._spawn_fn(idx, port)
        cmd = [a.replace("{port}", str(port)) for a in self._replica_cmd]
        env = dict(os.environ)
        if self._spawn_env:
            env.update(self._spawn_env)
        # the replica process learns its fleet role from the env the
        # api_server CLI resolves ($BIGDL_TPU_REPLICA_ROLE) — role
        # flips go through a drain-respawn, never a live mutation
        env["BIGDL_TPU_REPLICA_ROLE"] = role
        return subprocess.Popen(cmd, env=env,
                                stdout=subprocess.DEVNULL)

    def _respawn(self, r: Replica, initial: bool = False) -> None:
        r.generation += 1
        r.proc = self._spawn(r.idx, r.port, r.role)
        r.started_at = time.monotonic()
        r.probe_failures = 0
        r.breaker = "closed"
        r.breaker_failures = 0
        self._set_state(r, STARTING)
        if not initial:
            r.restarts += 1
            self._count("restarts")
            # replica idx is bounded by fleet size — audited
            self._c_restarts.labels(str(r.idx)).inc()  # graftlint: disable=metric-label-cardinality
        self.flight.record("replica_spawn", replica=r.idx, port=r.port,
                           pid=r.pid, generation=r.generation)

    def _set_state(self, r: Replica, state: str) -> None:
        if r.state != state:
            self.flight.record("replica_state", replica=r.idx,
                               prev=r.state, state=state)
        r.state = state
        # replica idx is bounded by fleet size — audited
        self._g_state.labels(str(r.idx)).set(  # graftlint: disable=metric-label-cardinality
            STATE_CODES[state])

    # -- supervision --------------------------------------------------------

    def _supervise(self) -> None:
        while not self._stop:
            try:
                self._tick()
            except Exception:
                import traceback

                traceback.print_exc()   # the supervisor must survive
            self._wake.wait(timeout=self.cfg.health_sec)
            self._wake.clear()

    def _tick(self) -> None:
        now = time.monotonic()
        for r in list(self.replicas):    # add_replica appends live
            if r.state in (QUARANTINED, RETIRED) or r.planned_restart:
                continue
            if r.state == BACKOFF:
                if now >= r.backoff_until:
                    self._respawn(r)
                continue
            if r.proc is not None and r.proc.poll() is not None:
                self._handle_death(
                    r, f"exit code {r.proc.returncode}")
                continue
            self._probe(r, now)

    @staticmethod
    def _fault_point(path: str) -> str:
        """Chaos scope for one fleet-internal HTTP call: the point=
        label net_latency / net_drop clauses select on."""
        if "/migrate" in path:
            return "migrate"
        if "/kv_handoff" in path:
            return "handoff"
        if path.startswith("/v1/admin"):
            return "admin"
        if path.startswith("/v1/completions") \
                or path.startswith("/v1/chat/"):
            return "canary"              # only the prober posts these
        return "stats"                   # /health, /v1/stats, spans

    def _net_fault(self, path: str) -> None:
        """Apply injected network chaos to one internal client call:
        sleep the scoped latency, then fail as a connection reset when
        a scoped drop fires (the caller's OSError handling — probe
        failure accounting, stats-poll skip, migrate fallback — is
        exactly the machinery under test)."""
        if not self.faults.enabled:
            return
        point = self._fault_point(path)
        step = next(self._fault_step)
        d = self.faults.net_delay_ms(point, step)
        if d > 0:
            time.sleep(d / 1000.0)
        if self.faults.net_dropped(point, step):
            raise OSError(
                f"injected connection reset (net_drop@{point})")

    def _http_get(self, port: int, path: str,
                  timeout: float) -> Tuple[int, bytes]:
        self._net_fault(path)
        conn = http.client.HTTPConnection(self.host, port,
                                          timeout=timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def _http_post(self, port: int, path: str, doc: dict,
                   timeout: float) -> Tuple[int, bytes]:
        self._net_fault(path)
        body = json.dumps(doc).encode()
        conn = http.client.HTTPConnection(self.host, port,
                                          timeout=timeout)
        try:
            conn.request("POST", path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def _probe(self, r: Replica, now: float) -> None:
        try:
            status, body = self._http_get(r.port, "/health",
                                          self.cfg.health_timeout_sec)
        except OSError:
            status, body = -1, b""
        if status == 200:
            r.probe_failures = 0
            if r.state != HEALTHY:
                self._set_state(r, HEALTHY)
            self._poll_stats(r)
            return
        detail = ""
        if status == 503:
            try:
                detail = json.loads(body).get("status", "")
            except (ValueError, AttributeError):
                detail = ""
        if detail == "draining":
            # expected while the replica finishes in-flight work
            # (rolling restart, operator SIGTERM); not a failure
            self._set_state(r, DRAINING)
            return
        # refused / timed out / wedged: the process may be alive but
        # the service is not there
        r.probe_failures += 1
        if r.state == STARTING:
            if now - r.started_at > self.cfg.spawn_timeout_sec:
                self._kill_hung(r, "never became healthy")
            return
        if r.state == HEALTHY:
            self._set_state(r, UNHEALTHY)
        if r.probe_failures >= self.cfg.unhealthy_after:
            self._kill_hung(
                r, f"hung ({r.probe_failures} probe failures"
                   f"{', ' + detail if detail else ''})")

    def canary_probe(self) -> None:
        """One canary probe was sent (counter hook for CanaryProber)."""
        self._count("canary_probes")
        self._c_canary_probes.inc()

    def canary_mismatch(self, r: Replica, kind: str, prompt_idx: int,
                        expected: str, got: str) -> None:
        """A golden-canary byte mismatch on replica ``r`` — a
        CORRECTNESS failure: the replica answers fast and healthy but
        wrong, so it is quarantined through the same supervisor path a
        crash loop takes (no restarts — wrong weights respawn wrong)
        and its process is terminated so in-flight requests fail over
        to byte-correct neighbors instead of finishing wrong."""
        self._count("canary_failures")
        # replica idx is bounded by fleet size — audited
        self._c_canary_fail.labels(str(r.idx)).inc()  # graftlint: disable=metric-label-cardinality
        self.flight.record(
            "canary_mismatch", replica=r.idx, kind=kind,
            prompt_idx=prompt_idx, expected=expected[:200],
            got=got[:200])
        if r.state == QUARANTINED:
            return                     # already isolated this sweep
        self._count("quarantined")
        self._set_state(r, QUARANTINED)
        self.flight.record("replica_quarantined", replica=r.idx,
                           reason="canary_mismatch", kind=kind)
        try:
            if r.proc is not None:
                r.proc.terminate()
        except Exception:
            pass

    def _kill_hung(self, r: Replica, reason: str) -> None:
        """A live-but-unresponsive replica (replica_hang, wedged step
        loop) is killed so its sockets break and in-flight requests can
        fail over — then handled exactly like a crash."""
        self.flight.record("replica_hung", replica=r.idx, reason=reason)
        try:
            if r.proc is not None:
                r.proc.kill()
                r.proc.wait(timeout=5)
        except Exception:
            pass
        self._handle_death(r, reason)

    def _handle_death(self, r: Replica, reason: str) -> None:
        now = time.monotonic()
        r.last_exit = reason
        r.deaths.append(now)
        orphaned = self.journal.inflight_on(r.idx)
        self.flight.record("replica_death", replica=r.idx, reason=reason,
                           inflight=len(orphaned))
        recent = [t for t in r.deaths
                  if now - t <= self.cfg.crash_window_sec]
        if len(recent) >= self.cfg.crash_budget:
            # crash loop: stop feeding it restarts — the replica-level
            # mirror of the engine's per-request crash-budget quarantine
            self._count("quarantined")
            self._set_state(r, QUARANTINED)
            self.flight.record("replica_quarantined", replica=r.idx,
                               deaths_in_window=len(recent),
                               window_sec=self.cfg.crash_window_sec)
            return
        backoff = min(self.cfg.backoff_max_sec,
                      self.cfg.backoff_base_sec * (2 ** (len(recent) - 1)))
        r.backoff_until = now + backoff
        self._set_state(r, BACKOFF)
        self.flight.record("replica_backoff", replica=r.idx,
                           backoff_sec=round(backoff, 3))

    def _poll_stats(self, r: Replica) -> None:
        """Occupancy for least-loaded fallback routing, plus the
        autoscaler's load signals (brownout, queue depth, tpot EWMA,
        ledger headroom) and the replica's handoff counters (turned
        into fleet-level deltas); best-effort."""
        try:
            status, body = self._http_get(r.port, "/v1/stats",
                                          self.cfg.health_timeout_sec)
            if status != 200:
                return
            doc = json.loads(body)
            slots = doc.get("slots", {})
            total = max(int(slots.get("total", 1)), 1)
            r.occupancy = float(slots.get("active", 0)) / total
            r.queue_depth = int(doc.get("queue_depth", 0))
            ov = doc.get("overload") or {}
            r.brownout = int(ov.get("brownout_level", 0))
            r.tenants = ov.get("tenants") or {}
            r.tpot_ewma_ms = float(ov.get("tpot_ewma_ms", 0.0))
            hr = (doc.get("memory") or {}).get("headroom") or {}
            hb, lim = hr.get("headroom_bytes"), hr.get("bytes_limit")
            r.headroom_frac = (float(hb) / float(lim)
                               if isinstance(hb, (int, float))
                               and isinstance(lim, (int, float))
                               and lim else None)
            ho = doc.get("handoff") or {}
            ho = {k: int(v) for k, v in ho.items()
                  if isinstance(v, (int, float))}
            # per-generation deltas: a respawned replica restarts its
            # counters at zero, so only compare within one generation
            prev = r.handoff if r.handoff_gen == r.generation else {}
            for key in ("retries", "fallbacks"):
                d = ho.get(key, 0) - prev.get(key, 0)
                if d > 0:
                    self._count(f"handoff_{key}", d)
            r.handoff = ho
            r.handoff_gen = r.generation
            mig = doc.get("migration")
            r.migration = mig if isinstance(mig, dict) else None
            mg = r.migration or {}
            wr = doc.get("wire_rejects") or {}
            cur = {
                "migration_committed":
                    int(mg.get("committed", 0) or 0),
                "migration_failed": int(mg.get("failed", 0) or 0),
                "migration_local_resume":
                    int(mg.get("local_resume", 0) or 0),
                "migration_imported":
                    int(mg.get("imported", 0) or 0),
                "migration_claimed": int(mg.get("claimed", 0) or 0),
                "migrated_tokens_total":
                    int(mg.get("migrated_tokens_total", 0) or 0),
                "recomputed_tokens_total":
                    int(mg.get("recomputed_tokens_total", 0) or 0),
                "wire_rejects": sum(
                    int(v) for v in wr.values()
                    if isinstance(v, (int, float))),
            }
            prevm = (r.migration_counts
                     if r.migration_gen == r.generation else {})
            for key, v in cur.items():
                d = v - prevm.get(key, 0)
                if d > 0:
                    self._count(key, d)
            r.migration_counts = cur
            r.migration_gen = r.generation
            self._maybe_brownout_migrate(r)
            perf = doc.get("perf")
            r.perf = perf if isinstance(perf, dict) else None
            quality = doc.get("quality")
            r.quality = quality if isinstance(quality, dict) else None
            slo = doc.get("slo")
            if isinstance(slo, dict):
                # compact fleet view; the full per-replica document
                # stays one proxy hop away at GET /v1/slo
                r.slo = {
                    "alerts_active": int(slo.get("alerts_active") or 0),
                    "alerts_total": int(slo.get("alerts_total") or 0),
                    "burn_rate_max": float(
                        slo.get("burn_rate_max") or 0.0),
                }
        except (OSError, ValueError):
            pass

    # -- circuit breaker ----------------------------------------------------

    def _breaker_failure(self, r: Replica) -> None:
        r.breaker_failures += 1
        if r.breaker == "half_open" or (
                r.breaker == "closed"
                and r.breaker_failures >= self.cfg.breaker_threshold):
            r.breaker = "open"
            r.breaker_open_until = (time.monotonic()
                                    + self.cfg.breaker_cooldown_sec)
            self._count("breaker_trips")
            # replica idx is bounded by fleet size — audited
            self._c_trips.labels(str(r.idx)).inc()  # graftlint: disable=metric-label-cardinality
            self.flight.record("breaker_open", replica=r.idx,
                               failures=r.breaker_failures)

    def _breaker_success(self, r: Replica) -> None:
        r.breaker_failures = 0
        if r.breaker != "closed":
            self.flight.record("breaker_close", replica=r.idx,
                               was=r.breaker)
            r.breaker = "closed"

    def _routable(self, r: Replica) -> bool:
        if r.state != HEALTHY or r.planned_restart:
            return False
        if r.breaker == "open":
            if time.monotonic() < r.breaker_open_until:
                return False
            # cooldown elapsed: half-open, admit a trial request
            r.breaker = "half_open"
            self.flight.record("breaker_half_open", replica=r.idx)
        return True

    # -- routing ------------------------------------------------------------

    def _affinity_key(self, body: dict) -> int:
        prompt = body.get("prompt")
        if prompt is None:
            msgs = body.get("messages") or []
            prompt = "\x00".join(
                f"{m.get('role', '')}:{m.get('content', '')}"
                for m in msgs)
        if isinstance(prompt, list):
            prefix = prompt[:self.cfg.affinity_tokens]
        else:
            prefix = str(prompt)[:self.cfg.affinity_tokens * 4]
        digest = hashlib.sha1(repr(prefix).encode()).digest()
        return int.from_bytes(digest[:8], "big")

    def _pick(self, key: int, exclude=()) -> Replica:
        """Prefix-affinity first: the consistent-hash target takes the
        request when it is routable and has a free slot (its prefix
        cache already holds this prompt family's entry); otherwise the
        least-loaded routable replica. Decode-role replicas are
        reserved for handoff decode work — they take client traffic
        only when NO other replica is routable (degraded fleet beats a
        503)."""
        n = len(self.replicas)
        candidates = [r for r in self.replicas
                      if r.idx not in exclude and self._routable(r)]
        if not candidates:
            raise NoReplica()
        front = [r for r in candidates if r.role != "decode"]
        if front:
            candidates = front
        affinity = self.replicas[key % n]
        # a browned-out replica is degrading service to protect itself:
        # prefix affinity is not worth routing INTO the pressure, and
        # the least-loaded fallback prefers the lowest brownout level
        if affinity in candidates and affinity.occupancy < 1.0 \
                and affinity.brownout == 0:
            return affinity
        return min(candidates,
                   key=lambda r: (r.brownout, r.occupancy,
                                  r.queue_depth, len(r.inflight), r.idx))

    def _pick_wait(self, key: int, exclude: Dict[int, int],
                   deadline: float) -> Replica:
        """``_pick`` that RIDES OUT a replica gap: with every replica
        momentarily unroutable (the last healthy one just died and its
        replacement is mid-spawn), keep polling until ``deadline``
        instead of failing the request. ``exclude`` maps replica idx ->
        the GENERATION that failed us: a respawned process at the same
        index is a new generation and gets forgiven, while the dead
        instance stays excluded even during the window where the
        supervisor still believes it healthy (state is probe-delayed;
        generation only moves on respawn)."""
        while True:
            try:
                return self._pick(key, exclude)
            except NoReplica:
                stale = [i for i, gen in exclude.items()
                         if self.replicas[i].generation != gen]
                for i in stale:
                    del exclude[i]
                if stale:
                    continue
                if time.monotonic() >= deadline:
                    raise
                time.sleep(min(0.05, self.cfg.health_sec))

    def retry_after_hint(self) -> int:
        """Seconds until a fresh replica is plausibly routable."""
        return max(1, int(round(2 * self.cfg.health_sec)))

    @staticmethod
    def _tenant_of(headers) -> Optional[str]:
        """Same identity derivation as the replica api_server (explicit
        X-Tenant-Id, else a stable API-key hash) so router-fronted and
        direct traffic land in the same per-tenant buckets."""
        tid = headers.get("X-Tenant-Id")
        if tid:
            return str(tid)[:64]
        auth = headers.get("Authorization")
        if auth:
            return "key-" + hashlib.sha256(
                auth.encode("utf-8", "replace")).hexdigest()[:12]
        return None

    # -- forwarding ---------------------------------------------------------

    def _handoff_targets(self, prefill: Replica) -> List[str]:
        """host:port decode candidates for a prefill replica's KV
        handoff, ordered least-loaded. Decode-role replicas first;
        with none routable, mixed replicas stand in (the prefill
        replica itself is never a target)."""
        cands = [r for r in self.replicas
                 if r is not prefill and self._routable(r)]
        pool = [r for r in cands if r.role == "decode"] \
            or [r for r in cands if r.role == "mixed"]
        pool.sort(key=lambda r: (r.brownout, r.occupancy,
                                 r.queue_depth, len(r.inflight), r.idx))
        return [f"{self.host}:{r.port}"
                for r in pool[:max(1, self.cfg.handoff_fanout)]]

    # -- live migration -----------------------------------------------------

    def _migrate_peers(self, r: Replica) -> List[str]:
        """host:port targets for replica ``r``'s in-flight sequences:
        every OTHER routable replica, least-loaded first."""
        peers = [x for x in self.replicas
                 if x is not r and self._routable(x)]
        peers.sort(key=lambda x: (x.brownout, x.occupancy,
                                  x.queue_depth, len(x.inflight),
                                  x.idx))
        return [f"{self.host}:{x.port}" for x in peers]

    def _migrate_off(self, r: Replica, reason: str,
                     qos: Optional[str] = None,
                     max_sequences: Optional[int] = None) -> dict:
        """Ask replica ``r`` to export its mid-decode sequences to
        healthy peers (POST /v1/admin/migrate_out) ahead of a planned
        disruption. Best-effort by design: a refused or failed call
        falls back to the plain SIGTERM drain — in-flight work
        finishes locally, zero 5xx, just not zero recompute if the
        process then dies."""
        targets = self._migrate_peers(r)
        out: dict = {"requested": False, "migrated": 0, "failed": 0}
        if not targets or not r.alive():
            return out
        doc: dict = {"targets": targets}
        if qos:
            doc["qos"] = qos
        if max_sequences:
            doc["max_sequences"] = int(max_sequences)
        try:
            status, body = self._http_post(
                r.port, "/v1/admin/migrate_out", doc,
                self.cfg.migrate_admin_timeout_sec)
            out["requested"] = True
            out["status"] = status
            try:
                res = json.loads(body)
            except ValueError:
                res = {}
            out["migrated"] = int(res.get("migrated", 0) or 0)
            out["failed"] = int(res.get("failed", 0) or 0)
            out["skipped"] = int(res.get("skipped", 0) or 0)
            self._count("migrations_requested")
            if out["migrated"]:
                self._count("sequences_migrated", out["migrated"])
            if out["failed"]:
                self._count("sequences_migrate_failed", out["failed"])
        except OSError as e:
            out["error"] = str(e)[:200]
        self.flight.record("migrate_off", replica=r.idx,
                           reason=reason, qos=qos, **out)
        return out

    def _maybe_brownout_migrate(self, r: Replica) -> None:
        """Brownout ladder, fleet rung: a replica that reached level 3
        is already degrading everyone it serves — when an idle peer
        exists, push a few batch-QoS sequences over instead of letting
        them starve behind the interactive tier. Rate-limited per
        replica; interactive traffic never moves this way (its KV is
        hot here; migration is for work that tolerates the hop)."""
        wants = bool((r.migration or {}).get("wants_migration")) \
            or r.brownout >= 3
        if not wants:
            return
        now = time.monotonic()
        if now - r.last_brownout_migrate \
                < self.cfg.brownout_migrate_interval_sec:
            return
        if not any(x is not r and self._routable(x)
                   and x.brownout == 0 for x in self.replicas):
            return                       # nowhere cooler to go
        r.last_brownout_migrate = now
        self._count("brownout_migrations")
        self._migrate_off(r, "brownout", qos="batch",
                          max_sequences=self.cfg.brownout_migrate_batch)

    def _replica_at(self, target: str) -> Optional[Replica]:
        """The replica serving ``host:port``, or None. State is NOT
        checked: a migration target just acked a stage, which beats a
        probe-delayed lifecycle label; a dead process fails the
        forward and the caller falls back."""
        try:
            port = int(str(target).rsplit(":", 1)[-1])
        except ValueError:
            return None
        for r in self.replicas:
            if r.port == port and r.alive():
                return r
        return None

    @staticmethod
    def _migrated_of(data: bytes) -> Optional[dict]:
        """Parse a replica's mid-decode migration handoff body
        ({"object": "migration", "migrated": true, "resume_id",
        "target", ...}); None for a normal completion."""
        if b'"migrated"' not in data[:256]:
            return None
        try:
            doc = json.loads(data)
        except ValueError:
            return None
        if isinstance(doc, dict) and doc.get("migrated") is True:
            return doc
        return None

    def _continue_migrated(self, entry: JournalEntry,
                           mig: dict) -> Tuple[int, bytes]:
        """A replica exported ``entry``'s sequence mid-decode: finish
        the request by re-POSTing the journaled ORIGINAL body to the
        migration target with ``X-Resume-Id``. The target claims the
        staged KV state, resumes at the exact sampler position, and
        returns the FULL completion (it detokenizes pre + post tokens
        together), so the client response is byte-identical to an
        unmigrated run. Chained hops (the target itself drains) loop,
        bounded by fleet size. Raises ``ReplicaLost`` when the staged
        state's home is gone — the caller replays from the journal."""
        hops = 0
        while True:
            resume_id = mig.get("resume_id")
            target = str(mig.get("target") or "")
            self._count("migration_continuations")
            self.journal.record_migration(entry.rid, resume_id,
                                          target)
            self.flight.record(
                "migration_continue", rid=entry.rid,
                resume_id=resume_id, target=target,
                **({"trace_id": entry.trace[0]}
                   if entry.trace is not None else {}))
            if entry.trace is not None:
                self.spans.annotate(
                    entry.trace[0], "migration_continue",
                    parent_id=entry.trace[2], target=target,
                    resume_id=resume_id, request_id=entry.rid)
            rep = self._replica_at(target)
            if rep is None or not resume_id:
                raise ReplicaLost(
                    f"migration target {target!r} not reachable")
            hdrs = self._fwd_headers(entry)
            hdrs["X-Resume-Id"] = str(resume_id)
            rep.inflight.add(entry.rid)
            self.journal.assign(entry.rid, rep.idx, rep.generation)
            conn = http.client.HTTPConnection(
                self.host, rep.port,
                timeout=self.cfg.connect_timeout_sec)
            try:
                conn.request("POST", entry.path, body=entry.body,
                             headers=hdrs)
                conn.sock.settimeout(self.cfg.forward_timeout_sec)
                resp = conn.getresponse()
                status, data = resp.status, resp.read()
            except (OSError, http.client.HTTPException) as e:
                self._breaker_failure(rep)
                raise ReplicaLost(
                    f"migration target {target}: "
                    f"{type(e).__name__}: {e}") from e
            finally:
                rep.inflight.discard(entry.rid)
                conn.close()
            nxt = self._migrated_of(data) if status == 200 else None
            if nxt is None:
                self._breaker_success(rep)
                return status, data
            mig = nxt
            hops += 1
            if hops > len(self.replicas) + 1:
                raise ReplicaLost("migration continuation loop")

    def _fwd_headers(self, entry: JournalEntry,
                     r: Optional[Replica] = None) -> Dict[str, str]:
        """Headers for a replica forward: the client's tenant identity
        must survive the hop or every request lands in the replica's
        shared 'default' rate-limit bucket. A non-streaming forward to
        a prefill-role replica also names its decode candidates
        (X-Handoff-Targets) — the replica prefills, ships KV to the
        first target it can reach, and relays the decode's answer."""
        h = {"Content-Type": "application/json"}
        if entry.tenant:
            h["X-Tenant-Id"] = entry.tenant
        if entry.trace is not None:
            # the replica parents its engine spans under the ROUTER
            # span, not the client's — replays re-forward the same id,
            # so every attempt lands on one timeline
            h["traceparent"] = make_traceparent(entry.trace[0],
                                                entry.trace[2])
        if r is not None and r.role == "prefill" and not entry.stream:
            targets = self._handoff_targets(r)
            if targets:
                h["X-Handoff-Targets"] = ",".join(targets)
        return h

    def _forward_buffered(self, r: Replica, entry: JournalEntry
                          ) -> Tuple[int, bytes]:
        """POST the journaled body to one replica and buffer the full
        response. Raises ``ReplicaLost`` on any transport failure — a
        SIGKILLed process closes its sockets, so every death mode ends
        here rather than in a client-visible hang."""
        rid = entry.rid
        r.inflight.add(rid)
        conn = http.client.HTTPConnection(
            self.host, r.port, timeout=self.cfg.connect_timeout_sec)
        try:
            conn.request("POST", entry.path, body=entry.body,
                         headers=self._fwd_headers(entry, r))
            conn.sock.settimeout(self.cfg.forward_timeout_sec)
            resp = conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException) as e:
            raise ReplicaLost(f"replica {r.idx}: "
                              f"{type(e).__name__}: {e}") from e
        finally:
            r.inflight.discard(rid)
            conn.close()

    def _forward_hedged(self, primary: Replica, entry: JournalEntry,
                        exclude: Dict[int, int]
                        ) -> Tuple[Replica, int, bytes]:
        """Primary forward, plus ONE duplicate on another replica when
        no response lands inside hedge_ms. First answer wins; the
        loser's closed connection triggers the replica engine's
        client-disconnect abort, freeing its slot."""
        hedge_ms = self.cfg.hedge_ms
        results: "queue.Queue" = queue.Queue()

        def run(rep: Replica):
            try:
                status, data = self._forward_buffered(rep, entry)
                results.put((rep, None, status, data))
            except ReplicaLost as e:
                results.put((rep, e, 0, b""))

        threading.Thread(target=run, args=(primary,), daemon=True).start()
        launched = 1
        if hedge_ms > 0 and not entry.stream:
            try:
                got = results.get(timeout=hedge_ms / 1000.0)
                results.put(got)       # not late: hand it back
            except queue.Empty:
                try:
                    second = self._pick(
                        entry.key, set(exclude) | {primary.idx})
                except NoReplica:
                    second = None
                if second is not None:
                    entry.hedged = True
                    self._count("hedges")
                    self._c_hedges.inc()
                    self.flight.record("hedge", rid=entry.rid,
                                       primary=primary.idx,
                                       hedge=second.idx)
                    if entry.trace is not None:
                        self.spans.annotate(
                            entry.trace[0], "hedge",
                            parent_id=entry.trace[2],
                            primary=primary.idx, hedge=second.idx,
                            request_id=entry.rid)
                    threading.Thread(target=run, args=(second,),
                                     daemon=True).start()
                    launched += 1
        err: Optional[ReplicaLost] = None
        err_rep = primary
        for _ in range(launched):
            rep, e, status, data = results.get()
            if e is None:
                return rep, status, data
            err, err_rep = e, rep
            self._breaker_failure(rep)
        raise ReplicaLost(str(err)) from err

    # -- request paths ------------------------------------------------------

    def route_buffered(self, entry: JournalEntry) -> Tuple[int, bytes]:
        """Non-streaming path: forward, and on replica loss REPLAY the
        journaled request on a healthy replica (up to max_replays).
        A replica's own 503 (drain race) re-routes without burning the
        replay budget — that is the rolling restart's zero-5xx leg."""
        t0 = time.monotonic()
        pick_deadline = t0 + self.cfg.no_replica_wait_sec
        exclude: Dict[int, int] = {}
        reroutes = 0
        while True:
            try:
                r = self._pick_wait(entry.key, exclude, pick_deadline)
            except NoReplica:
                return 503, json.dumps({"error": {
                    "message": "no healthy replica; retry shortly",
                    "type": "unavailable", "code": 503,
                    "retry_after": self.retry_after_hint()}}).encode()
            self.journal.assign(entry.rid, r.idx, r.generation)
            try:
                used, status, data = self._forward_hedged(
                    r, entry, exclude)
            except ReplicaLost as e:
                exclude[r.idx] = r.generation
                self._count("failovers")
                self._c_failovers.inc()
                self.flight.record(
                    "failover", rid=entry.rid, replica=r.idx,
                    error=str(e)[:200],
                    **({"trace_id": entry.trace[0]}
                       if entry.trace is not None else {}))
                if entry.trace is not None:
                    self.spans.annotate(
                        entry.trace[0], "failover",
                        parent_id=entry.trace[2], replica=r.idx,
                        request_id=entry.rid, error=str(e)[:120])
                if entry.replays < self.cfg.max_replays:
                    entry.replays += 1
                    self._count("replays")
                    self._c_replays.inc()
                    self.flight.record("replay", rid=entry.rid,
                                       attempt=entry.replays)
                    if entry.trace is not None:
                        self.spans.annotate(
                            entry.trace[0], "failover_replay",
                            parent_id=entry.trace[2],
                            attempt=entry.replays,
                            request_id=entry.rid)
                    continue
                return 502, json.dumps({"error": {
                    "message": "replica failed and replay budget is "
                               "spent", "type": "replica_lost",
                    "code": 502, "replays": entry.replays,
                    "retry_after": self.retry_after_hint()}}).encode()
            if status == 429:
                # per-tenant rate limit: every replica enforces the
                # same tenant budget, so re-routing would just evade
                # it — propagate verbatim (Retry-After preserved by
                # the handler), no replay burn, no breaker hit
                self._breaker_success(used)
                self._count("shed_429")
                self.flight.record("shed_429", rid=entry.rid,
                                   replica=used.idx,
                                   tenant=entry.tenant or "default")
                self._count("requests")
                # idx bounded by fleet size, status by HTTP codes
                self._c_requests.labels(
                    str(used.idx), str(status)).inc()  # graftlint: disable=metric-label-cardinality
                return status, data
            if status == 503:
                # the replica is shedding (drain race or overload):
                # someone else takes it; re-route burns no replay
                # budget — only when every replica shed does the 503
                # reach the client
                exclude[used.idx] = used.generation
                reroutes += 1
                self._count("rerouted_503")
                self.flight.record("reroute_503", rid=entry.rid,
                                   replica=used.idx)
                if reroutes <= len(self.replicas):
                    continue
                return 503, data
            if status == 200:
                mig = self._migrated_of(data)
                if mig is not None:
                    # the sequence moved mid-decode (drain, restart,
                    # scale-down, brownout): finish it on its new home
                    self._breaker_success(used)
                    try:
                        status, data = self._continue_migrated(
                            entry, mig)
                        # continuation served by the TARGET: count and
                        # return here so a rare target-side 5xx does
                        # not land on the source's breaker
                        self._count("requests")
                        # idx bounded by fleet size, status by HTTP
                        self._c_requests.labels(
                            str(used.idx), str(status)).inc()  # graftlint: disable=metric-label-cardinality
                        self._h_latency.observe(
                            time.monotonic() - t0)
                        return status, data
                    except ReplicaLost as e:
                        # the staged state died with its target: fall
                        # back to a full journal replay — recomputes
                        # the prefix, never wrong
                        self._count("migration_fallback_replays")
                        self.flight.record(
                            "migration_fallback", rid=entry.rid,
                            error=str(e)[:200])
                        if entry.replays < self.cfg.max_replays:
                            entry.replays += 1
                            self._count("replays")
                            self._c_replays.inc()
                            continue
                        return 502, json.dumps({"error": {
                            "message": "migration continuation failed "
                                       "and replay budget is spent",
                            "type": "replica_lost", "code": 502,
                            "retry_after":
                                self.retry_after_hint()}}).encode()
            if status >= 500:
                self._breaker_failure(used)
            else:
                self._breaker_success(used)
            self._count("requests")
            # idx bounded by fleet size, status by HTTP codes
            self._c_requests.labels(
                str(used.idx), str(status)).inc()  # graftlint: disable=metric-label-cardinality
            self._h_latency.observe(time.monotonic() - t0)
            return status, data

    # streaming handled in the HTTP handler (needs the client socket)

    # -- rolling restart ----------------------------------------------------

    def rolling_restart(self) -> dict:
        """Drain + respawn replicas ONE AT A TIME: stop routing to the
        replica, SIGTERM it (the api_server's drain finishes in-flight
        work, then the process exits), respawn, wait healthy, move on.
        Raises ``RuntimeError`` when already in progress."""
        if not self._admin_lock.acquire(blocking=False):
            raise RuntimeError("rolling restart already in progress")
        t0 = time.monotonic()
        results = []
        self._rolling = True
        self.flight.record("rolling_restart_begin",
                           replicas=len(self.replicas))
        try:
            for r in self.replicas:
                if r.state == QUARANTINED:
                    results.append({"replica": r.idx,
                                    "skipped": "quarantined"})
                    continue
                r.planned_restart = True   # the supervisor hands over
                self._set_state(r, DRAINING)
                step = {"replica": r.idx, "pid": r.pid}
                # live migration BEFORE the SIGTERM: mid-decode
                # sequences move to healthy peers (the in-flight
                # relays see the migrated marker and re-forward), so
                # the drain has nothing left to wait out and the
                # restart costs zero recomputed tokens — a refused or
                # failed migrate falls back to the plain drain
                step["migrate"] = self._migrate_off(
                    r, "rolling_restart")
                try:
                    if r.proc is not None and r.proc.poll() is None:
                        r.proc.terminate()     # SIGTERM -> drain
                        try:
                            r.proc.wait(
                                timeout=self.cfg.drain_exit_timeout_sec)
                        except Exception:
                            r.proc.kill()
                            r.proc.wait(timeout=5)
                            step["forced_kill"] = True
                    self._respawn(r)
                    if not self._wait_healthy(
                            r, self.cfg.spawn_timeout_sec):
                        step["error"] = ("replacement never became "
                                         "healthy")
                        results.append(step)
                        break
                    step["ok"] = True
                    results.append(step)
                finally:
                    r.planned_restart = False
            return {"rolling_restart": results,
                    "duration_s": round(time.monotonic() - t0, 3),
                    "ok": all(s.get("ok") or s.get("skipped")
                              for s in results)}
        finally:
            self._rolling = False
            self.flight.record("rolling_restart_end")
            self._admin_lock.release()

    def fleet_profiler(self, body: Optional[dict] = None) -> dict:
        """``POST /v1/admin/profiler``: fan a time-boxed jax.profiler
        capture out to every routable replica SIMULTANEOUSLY (the
        interesting regressions are fleet-synchronized: a noisy
        neighbor, a host stall, a bad deploy hits every replica in
        the same second). Each replica captures into its own subdir of
        ``log_dir`` and auto-stops at ``duration_sec`` (clamped to
        ``BIGDL_TPU_PROFILER_MAX_SEC``) via the profiler watchdog — no
        stop fan-out needed. The whole capture is stitched to one fleet
        ``capture_id`` (a trace id), recorded as a router span so
        ``GET /v1/trace/{capture_id}`` shows who captured what.
        Raises ``RuntimeError`` when an admin operation is already in
        progress, ``ValueError`` on a bad duration."""
        body = body or {}
        duration = body.get("duration_sec")
        if duration is not None:
            try:
                duration = float(duration)
            except (TypeError, ValueError):
                raise ValueError(
                    f"duration_sec must be a positive number, got "
                    f"{body.get('duration_sec')!r}")
            if duration <= 0:
                raise ValueError(
                    f"duration_sec must be a positive number, got "
                    f"{duration}")
        log_dir = body.get("log_dir") or os.path.join(
            os.environ.get("BIGDL_TPU_POSTMORTEM_DIR") or "/tmp",
            "fleet_profiler")
        if not os.path.isabs(log_dir):
            raise ValueError(
                f"log_dir must be an absolute path, got {log_dir!r}")
        if not self._admin_lock.acquire(blocking=False):
            raise RuntimeError("an admin operation is already in "
                               "progress")
        try:
            capture_id = new_trace_id()
            t0 = time.time()
            targets = [r for r in self.replicas
                       if r.state == HEALTHY and r.alive()]
            self.flight.record("fleet_profiler_begin",
                               capture_id=capture_id,
                               replicas=[r.idx for r in targets],
                               log_dir=log_dir,
                               duration_sec=duration)
            # one thread per replica: the whole point is that every
            # replica's capture brackets the SAME wall-clock window
            # (profiler init can take seconds — serial fan-out would
            # stagger the windows by that much per replica)
            results = []
            for r in targets:
                sub = os.path.join(log_dir, capture_id,
                                   f"replica{r.idx}")
                results.append({"replica": r.idx, "port": r.port,
                                "log_dir": sub})

            def _start_one(r, row):
                doc = {"log_dir": row["log_dir"],
                       "capture_id": capture_id}
                if duration is not None:
                    doc["duration_sec"] = duration
                try:
                    status, raw = self._http_post(
                        r.port, "/v1/profiler/start", doc,
                        max(self.cfg.health_timeout_sec, 15.0))
                    row["status"] = status
                    try:
                        row["body"] = json.loads(raw)
                    except ValueError:
                        pass
                    row["ok"] = status == 200
                except OSError as e:
                    row["ok"] = False
                    row["error"] = str(e)

            threads = [threading.Thread(target=_start_one, args=tr,
                                        daemon=True)
                       for tr in zip(targets, results)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30.0)
            for r, row in zip(targets, results):
                row.setdefault("ok", False)
                self.spans.record(
                    "fleet_capture", capture_id,
                    t_start=t0, t_end=time.time(),
                    replica=r.idx, port=r.port,
                    log_dir=row["log_dir"], ok=row["ok"])
            started = sum(1 for row in results if row.get("ok"))
            self._count("fleet_profiler_captures", started)
            self.flight.record("fleet_profiler_end",
                               capture_id=capture_id, started=started,
                               replicas=len(results))
            return {"capture_id": capture_id, "log_dir": log_dir,
                    "duration_sec": duration, "replicas": results,
                    "started": started, "ok": started == len(results)
                    and bool(results)}
        finally:
            self._admin_lock.release()

    def _wait_healthy(self, r: Replica, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if r.proc is not None and r.proc.poll() is not None:
                return False
            try:
                status, _ = self._http_get(r.port, "/health",
                                           self.cfg.health_timeout_sec)
                if status == 200:
                    r.probe_failures = 0
                    self._set_state(r, HEALTHY)
                    return True
            except OSError:
                pass
            time.sleep(min(0.1, self.cfg.health_sec))
        return False

    # -- fleet mutation (autoscaler) ----------------------------------------
    #
    # All three mutators are called with self._admin_lock HELD by the
    # caller (the autoscaler tick) — the same lock rolling_restart
    # takes, so a scale decision can never race a rolling restart.
    # Replicas are NEVER removed from self.replicas (routing holds
    # positional idx lookups); a retired replica stays in the list in
    # the terminal RETIRED state, which the supervisor skips.

    def add_replica(self, role: str = "mixed") -> Replica:
        """Grow the fleet by one replica (scale-up). Returns the new
        Replica immediately (state STARTING); the supervisor's probe
        loop promotes it to HEALTHY once /health answers."""
        if role not in ROLES:
            raise ValueError(f"unknown replica role {role!r}")
        r = Replica(len(self.replicas), _free_port(self.host),
                    role=role)
        self._respawn(r, initial=True)
        self.replicas.append(r)
        self._count("autoscale_spawned")
        self.flight.record("replica_added", replica=r.idx,
                           port=r.port, role=role)
        return r

    def retire_replica(self, r: Replica,
                       reason: str = "autoscale") -> bool:
        """Drain and permanently remove one replica (scale-down):
        routing stops immediately, SIGTERM runs the api_server's
        graceful drain, and the slot is left in the terminal RETIRED
        state. Returns False WITHOUT touching the process when the
        replica is the last healthy one (a fleet of zero serves
        nothing) or is not in a retirable state."""
        healthy_others = [x for x in self.replicas
                          if x is not r and x.state == HEALTHY
                          and not x.planned_restart]
        if r.state != HEALTHY or r.planned_restart \
                or not healthy_others:
            self._count("autoscale_refused")
            self.flight.record(
                "retire_refused", replica=r.idx,
                reason=("last_healthy" if not healthy_others
                        else f"state:{r.state}"))
            return False
        r.planned_restart = True         # supervisor hands the proc over
        self._set_state(r, DRAINING)
        # scale-down is a planned disruption: move the mid-decode
        # sequences to surviving replicas first, then drain whatever
        # (if anything) refused to export
        mig = self._migrate_off(r, reason)
        try:
            if r.proc is not None and r.proc.poll() is None:
                r.proc.terminate()       # SIGTERM -> graceful drain
                try:
                    r.proc.wait(timeout=self.cfg.drain_exit_timeout_sec)
                except Exception:
                    try:
                        r.proc.kill()
                        r.proc.wait(timeout=5)
                    except Exception:
                        pass
        finally:
            self._set_state(r, RETIRED)
            r.planned_restart = False
        self._count("autoscale_retired")
        self.flight.record("replica_retired", replica=r.idx,
                           reason=reason,
                           migrated=mig.get("migrated", 0))
        return True

    def reassign_role(self, r: Replica, role: str) -> bool:
        """Flip one replica's fleet role via drain + respawn (the role
        is a process property, resolved from the spawn env — never
        mutated live). Refuses on the last healthy replica: the flip
        makes it unavailable for a spawn cycle."""
        if role not in ROLES:
            raise ValueError(f"unknown replica role {role!r}")
        healthy_others = [x for x in self.replicas
                          if x is not r and x.state == HEALTHY
                          and not x.planned_restart]
        if r.state != HEALTHY or r.planned_restart \
                or not healthy_others:
            self._count("autoscale_refused")
            self.flight.record("role_flip_refused", replica=r.idx,
                               role=role)
            return False
        prev = r.role
        r.planned_restart = True
        self._set_state(r, DRAINING)
        try:
            if r.proc is not None and r.proc.poll() is None:
                r.proc.terminate()
                try:
                    r.proc.wait(timeout=self.cfg.drain_exit_timeout_sec)
                except Exception:
                    try:
                        r.proc.kill()
                        r.proc.wait(timeout=5)
                    except Exception:
                        pass
            r.role = role
            self._respawn(r)
            ok = self._wait_healthy(r, self.cfg.spawn_timeout_sec)
        finally:
            r.planned_restart = False
        self._count("autoscale_role_flips")
        self.flight.record("replica_role_flip", replica=r.idx,
                           prev=prev, role=role, ok=ok)
        return ok

    # -- distributed-trace fan-out ------------------------------------------

    def trace_timeline(self, trace_id: str) -> dict:
        """The ``GET /v1/trace/{id}`` document: this router's own spans
        plus every replica's (``GET /v1/internal/spans?trace_id=``),
        stitched by ``merge_timeline`` with a per-replica clock-skew
        estimate (local midpoint of the fan-out RTT minus the replica's
        reported ``now``)."""
        groups: List[Tuple[float, List[dict]]] = [
            (0.0, self.spans.spans_for(trace_id))]
        for r in self.replicas:
            if not r.alive():
                continue
            try:
                t_req0 = time.time()
                status, body = self._http_get(
                    r.port, f"/v1/internal/spans?trace_id={trace_id}",
                    self.cfg.health_timeout_sec)
                t_req1 = time.time()
                if status != 200:
                    continue
                doc = json.loads(body)
                skew = ((t_req0 + t_req1) / 2.0
                        - float(doc.get("now", t_req1)))
                groups.append((skew, doc.get("spans") or []))
            except (OSError, ValueError):
                continue
        # a client-supplied parent span lives outside the fleet: spans
        # pointing at it are NOT orphans
        ext = [s["parent_id"] for s in self.spans.spans_for(trace_id)
               if s.get("name") == "router.request"
               and s.get("parent_id")]
        return merge_timeline(trace_id, groups, external_parents=ext)

    def trace_index(self, k: int = 16) -> List[dict]:
        """The ``GET /v1/traces`` list: recent slow traces (top-k by
        duration) merged across the router and every live replica."""
        best: Dict[str, dict] = {}

        def take(t: dict) -> None:
            tid = t.get("trace_id")
            cur = best.get(tid)
            if cur is None or t.get("duration_s", 0.0) \
                    > cur.get("duration_s", 0.0):
                best[tid] = t

        for t in self.spans.recent_traces(k):
            take(t)
        for r in self.replicas:
            if not r.alive():
                continue
            try:
                status, body = self._http_get(
                    r.port, "/v1/internal/spans",
                    self.cfg.health_timeout_sec)
                if status != 200:
                    continue
                for t in json.loads(body).get("traces") or []:
                    take(t)
            except (OSError, ValueError):
                continue
        out = sorted(best.values(),
                     key=lambda d: -d.get("duration_s", 0.0))
        return out[:max(k, 0)]

    # -- introspection ------------------------------------------------------

    def _tenant_aggregate(self) -> dict:
        """Fleet-wide per-tenant counters: the sum of every replica's
        probed overload.tenants block (admitted/shed/generated)."""
        agg: Dict[str, collections.Counter] = {}
        for r in self.replicas:
            for name, t in (r.tenants or {}).items():
                acc = agg.setdefault(str(name), collections.Counter())
                for k, v in t.items():
                    if isinstance(v, (int, float)):
                        acc[k] += v
        return {name: dict(c) for name, c in sorted(agg.items())}

    def _count(self, key: str, n: int = 1) -> None:
        """Bump a stats counter (thread-safe: supervisor + handlers)."""
        with self._lock:
            self.counts[key] += n

    def counts_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {k: int(v) for k, v in sorted(self.counts.items())}

    def _perf_aggregate(self) -> dict:
        """Fleet roofline view from the per-replica /v1/stats perf
        blocks: per-replica utils plus fleet min/mean (the min is the
        alarm — one replica off the roof drags every hedged request)
        and the count of tripped sentinels."""
        per: Dict[str, dict] = {}
        utils: List[float] = []
        tripped = 0
        for r in self.replicas:
            if not r.perf:
                continue
            per[str(r.idx)] = dict(r.perf)
            u = r.perf.get("roofline_util_decode")
            if isinstance(u, (int, float)):
                utils.append(float(u))
            if r.perf.get("sentinel_tripped"):
                tripped += 1
        out: dict = {"replicas": per, "sentinels_tripped": tripped}
        if utils:
            out["decode_util_min"] = round(min(utils), 4)
            out["decode_util_mean"] = round(
                sum(utils) / len(utils), 4)
        return out

    def _quality_aggregate(self) -> dict:
        """Fleet quality view from the per-replica /v1/stats quality
        blocks: per-replica NLL/probe numbers plus the fleet's worst
        probe NLL (one silently-degraded replica is the alarm — it
        serves wrong-but-plausible tokens at full speed) and the count
        of tripped quality sentinels."""
        per: Dict[str, dict] = {}
        probe_nlls: List[float] = []
        tripped = 0
        for r in self.replicas:
            if not r.quality:
                continue
            per[str(r.idx)] = dict(r.quality)
            pn = r.quality.get("probe_nll")
            if isinstance(pn, (int, float)):
                probe_nlls.append(float(pn))
            if r.quality.get("sentinel_tripped"):
                tripped += 1
        out: dict = {"replicas": per, "sentinels_tripped": tripped}
        if probe_nlls:
            out["probe_nll_max"] = round(max(probe_nlls), 4)
            out["probe_nll_mean"] = round(
                sum(probe_nlls) / len(probe_nlls), 4)
        return out

    def _slo_aggregate(self) -> dict:
        """Fleet SLO view from the per-replica /v1/stats slo blocks:
        total active alerts and the worst burn rate anywhere (one
        replica burning its budget is the fleet's page), plus the
        canary prober's correctness state."""
        per: Dict[str, dict] = {}
        alerts_active = alerts_total = 0
        burn_max = 0.0
        for r in self.replicas:
            if not r.slo:
                continue
            per[str(r.idx)] = dict(r.slo)
            alerts_active += int(r.slo.get("alerts_active") or 0)
            alerts_total += int(r.slo.get("alerts_total") or 0)
            bm = r.slo.get("burn_rate_max")
            if isinstance(bm, (int, float)):
                burn_max = max(burn_max, float(bm))
        return {
            "replicas": per,
            "alerts_active": alerts_active,
            "alerts_total": alerts_total,
            "burn_rate_max": round(burn_max, 4),
            "canary": self.canary.snapshot(),
        }

    def _migration_aggregate(self) -> dict:
        """Fleet live-migration view: the sum of every replica's
        probed counters (per-generation deltas keep respawn resets
        from double-counting) plus live staging depth."""
        agg = collections.Counter()
        staged = pending = 0
        for r in self.replicas:
            for k, v in r.migration_counts.items():
                agg[k] += v
            mg = r.migration or {}
            staged += int(mg.get("staged", 0) or 0)
            pending += int(mg.get("pending_out", 0) or 0)
        return {**{k: int(v) for k, v in sorted(agg.items())},
                "staged": staged, "pending_out": pending}

    def stats_snapshot(self) -> dict:
        """JSON-ready router state for ``GET /v1/router/stats`` (and
        the bench JSON's ``router`` block)."""
        return {
            "replicas": [r.snapshot() for r in self.replicas],
            "journal_depth": self.journal.depth(),
            "journal": self.journal.snapshot(),
            "migration": self._migration_aggregate(),
            "spans": self.spans.snapshot(),
            "tenants": self._tenant_aggregate(),
            "counters": self.counts_snapshot(),
            "rolling_restart_in_progress": self._rolling,
            "perf": self._perf_aggregate(),
            "quality": self._quality_aggregate(),
            "slo": self._slo_aggregate(),
            "roles": {ro: sum(1 for r in self.replicas
                              if r.role == ro and r.state == HEALTHY)
                      for ro in ROLES},
            "autoscaler": (self.autoscaler.snapshot()
                           if self.autoscaler is not None else None),
            "config": {
                "replicas": self.cfg.replicas,
                "health_sec": self.cfg.health_sec,
                "hedge_ms": self.cfg.hedge_ms,
                "crash_budget": self.cfg.crash_budget,
                "canary_sec": self.cfg.canary_sec,
                "breaker_threshold": self.cfg.breaker_threshold,
                "max_replays": self.cfg.max_replays,
                "affinity_tokens": self.cfg.affinity_tokens,
                "handoff_fanout": self.cfg.handoff_fanout,
                "journal_path": self.cfg.journal_path,
            },
        }

    # -- http front ---------------------------------------------------------

    def make_handler(router):
        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):   # quiet
                pass

            def _json(self, code: int, obj, headers=()):
                body = obj if isinstance(obj, bytes) \
                    else json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in headers:
                    self.send_header(k, v)
                self.end_headers()
                try:
                    self.wfile.write(body)
                except OSError:
                    pass

            def _proxy(self, method: str, body: Optional[bytes] = None):
                """Pass non-completion traffic (models, stats, memory,
                metrics-of-replica, profiler) to any routable replica."""
                try:
                    r = router._pick(0)
                except NoReplica:
                    return self._json(503, {"error": {
                        "message": "no healthy replica",
                        "type": "unavailable", "code": 503}})
                conn = http.client.HTTPConnection(
                    router.host, r.port,
                    timeout=router.cfg.forward_timeout_sec)
                try:
                    conn.request(method, self.path, body=body,
                                 headers={"Content-Type":
                                          "application/json"})
                    resp = conn.getresponse()
                    data = resp.read()
                    ctype = resp.getheader("Content-Type",
                                           "application/json")
                    self.send_response(resp.status)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                except (OSError, http.client.HTTPException) as e:
                    self._json(502, {"error": {
                        "message": f"replica proxy failed: {e}",
                        "type": "replica_lost", "code": 502}})
                finally:
                    conn.close()

            def do_GET(self):
                if self.path in ("/health", "/ping"):
                    n = sum(1 for r in router.replicas
                            if router._routable(r))
                    if n:
                        self._json(200, {"status": "ok",
                                         "routable_replicas": n})
                    else:
                        self._json(
                            503,
                            {"status": "no_healthy_replica",
                             "retry_after": router.retry_after_hint()},
                            headers=(("Retry-After",
                                      str(router.retry_after_hint())),))
                elif self.path == "/metrics":
                    body = router.registry.render().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/v1/router/stats":
                    self._json(200, router.stats_snapshot())
                elif self.path == "/v1/router/flight":
                    self._json(200, {"events":
                                     router.flight.snapshot()})
                elif self.path.startswith("/v1/trace/"):
                    tid = self.path[len("/v1/trace/"):].split("?")[0]
                    self._json(200, router.trace_timeline(tid))
                elif self.path == "/v1/traces" \
                        or self.path.startswith("/v1/traces?"):
                    from urllib.parse import parse_qs, urlparse

                    q = parse_qs(urlparse(self.path).query)
                    try:
                        k = int((q.get("k") or ["16"])[0])
                    except ValueError:
                        k = 16
                    self._json(200, {"traces": router.trace_index(k)})
                else:
                    self._proxy("GET")

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n) if n else b"{}"
                if self.path == "/v1/admin/rolling_restart":
                    try:
                        out = router.rolling_restart()
                    except RuntimeError as e:
                        return self._json(409, {"error": str(e)})
                    return self._json(200 if out.get("ok") else 500,
                                      out)
                if self.path == "/v1/admin/profiler":
                    try:
                        body = json.loads(raw or b"{}")
                    except json.JSONDecodeError:
                        return self._json(400, {"error": "bad json"})
                    try:
                        out = router.fleet_profiler(body)
                    except ValueError as e:
                        return self._json(400, {"error": str(e)})
                    except RuntimeError as e:
                        return self._json(409, {"error": str(e)})
                    return self._json(200 if out.get("ok") else 500,
                                      out)
                if self.path not in ("/v1/completions",
                                     "/v1/chat/completions"):
                    return self._proxy("POST", raw)
                try:
                    body = json.loads(raw or b"{}")
                except json.JSONDecodeError:
                    return self._json(400, {"error": "bad json"})
                # trace context: accept the client's traceparent or
                # mint a fresh trace id; the tail-sampling decision is
                # a pure function of the id, so every replica agrees
                client = parse_traceparent(
                    self.headers.get("traceparent"))
                tid, parent = client if client is not None \
                    else (new_trace_id(), None)
                trace = ((tid, parent, new_span_id())
                         if trace_sampled(tid, router.spans.sample)
                         else None)
                entry = JournalEntry(
                    rid=f"rtr-{uuid.uuid4().hex[:12]}",
                    path=self.path, body=raw,
                    stream=bool(body.get("stream")),
                    key=router._affinity_key(body),
                    tenant=router._tenant_of(self.headers),
                    trace=trace)
                router.journal.admit(entry)   # write-ahead
                t_req0 = time.time()
                status = None
                try:
                    if entry.stream:
                        self._stream(entry)
                    else:
                        status, data = router.route_buffered(entry)
                        headers = ()
                        if status in (429, 503):
                            headers = _retry_after_headers(data) or (
                                ("Retry-After",
                                 str(router.retry_after_hint())),)
                        if entry.trace is not None:
                            headers = tuple(headers) + (
                                ("X-Trace-Id", entry.trace[0]),)
                        self._json(status, data, headers=headers)
                finally:
                    router.journal.complete(entry.rid)
                    if entry.trace is not None:
                        router.spans.record(
                            "router.request", entry.trace[0],
                            span_id=entry.trace[2],
                            parent_id=entry.trace[1],
                            t_start=t_req0, t_end=time.time(),
                            request_id=entry.rid, path=self.path,
                            stream=entry.stream,
                            replays=entry.replays,
                            hedged=entry.hedged,
                            **({"status": status}
                               if status is not None else {}))

            def _stream(self, entry: JournalEntry):
                """Relay SSE from the replica. A replica lost BEFORE
                any byte reached the client re-routes invisibly; lost
                MID-STREAM, the client gets a structured error event
                plus [DONE] instead of a dropped socket (generation is
                not transparently resumable — the client resubmits
                after retry_after)."""
                exclude: Dict[int, int] = {}
                reroutes = 0
                pick_deadline = (time.monotonic()
                                 + router.cfg.no_replica_wait_sec)
                while True:
                    try:
                        r = router._pick_wait(entry.key, exclude,
                                              pick_deadline)
                    except NoReplica:
                        return self._json(503, {"error": {
                            "message": "no healthy replica",
                            "type": "unavailable", "code": 503,
                            "retry_after": router.retry_after_hint()}})
                    router.journal.assign(entry.rid, r.idx,
                                          r.generation)
                    r.inflight.add(entry.rid)
                    conn = http.client.HTTPConnection(
                        router.host, r.port,
                        timeout=router.cfg.connect_timeout_sec)
                    try:
                        try:
                            conn.request(
                                "POST", entry.path, body=entry.body,
                                headers=router._fwd_headers(entry, r))
                            conn.sock.settimeout(
                                router.cfg.forward_timeout_sec)
                            resp = conn.getresponse()
                        except (OSError,
                                http.client.HTTPException) as e:
                            # nothing streamed yet: invisible failover
                            router._breaker_failure(r)
                            exclude[r.idx] = r.generation
                            router._count("failovers")
                            router._c_failovers.inc()
                            router.flight.record(
                                "failover", rid=entry.rid,
                                replica=r.idx, error=str(e)[:200],
                                **({"trace_id": entry.trace[0]}
                                   if entry.trace is not None
                                   else {}))
                            if entry.trace is not None:
                                router.spans.annotate(
                                    entry.trace[0], "failover",
                                    parent_id=entry.trace[2],
                                    replica=r.idx,
                                    request_id=entry.rid)
                            if entry.replays < router.cfg.max_replays:
                                entry.replays += 1
                                router._count("replays")
                                router._c_replays.inc()
                                if entry.trace is not None:
                                    router.spans.annotate(
                                        entry.trace[0],
                                        "failover_replay",
                                        parent_id=entry.trace[2],
                                        attempt=entry.replays,
                                        request_id=entry.rid)
                                continue
                            return self._json(502, {"error": {
                                "message": "replica failed before the "
                                           "stream started",
                                "type": "replica_lost", "code": 502}})
                        if resp.status == 429:
                            # tenant rate limit: same budget on every
                            # replica — propagate, don't re-route
                            data = resp.read()
                            router._breaker_success(r)
                            router._count("shed_429")
                            return self._json(
                                429, data,
                                headers=_retry_after_headers(data))
                        if resp.status == 503 \
                                and reroutes <= len(router.replicas):
                            resp.read()
                            exclude[r.idx] = r.generation
                            reroutes += 1
                            router._count("rerouted_503")
                            continue
                        if resp.status != 200:
                            data = resp.read()
                            router._breaker_failure(r) \
                                if resp.status >= 500 \
                                else router._breaker_success(r)
                            return self._json(resp.status, data)
                        # 200: stream is live — relay line-wise
                        router._breaker_success(r)
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "text/event-stream")
                        self.send_header("Cache-Control", "no-cache")
                        self.end_headers()
                        self._relay(entry, r, resp)
                        return
                    finally:
                        r.inflight.discard(entry.rid)
                        conn.close()

            def _pump(self, entry: JournalEntry, resp):
                """Relay one replica's SSE to the client until EOF.
                Returns (saw_done, migrated_info_or_None,
                client_gone). The mid-decode migration marker (a
                ``data: {"migrated": ...}`` event the replica emits
                INSTEAD of [DONE]) is consumed here — it is
                router-internal routing state, never client bytes."""
                saw_done = False
                mig = None
                try:
                    while True:
                        line = resp.fp.readline()
                        if not line:
                            break
                        s = line.strip()
                        if s == b"data: [DONE]":
                            saw_done = True
                        elif s.startswith(b'data: {"migrated"'):
                            try:
                                doc = json.loads(s[len(b"data: "):])
                            except ValueError:
                                doc = {}
                            got = doc.get("migrated")
                            if isinstance(got, dict):
                                mig = got
                                continue
                        try:
                            self.wfile.write(line)
                            if line == b"\n":
                                self.wfile.flush()
                        except OSError:
                            # CLIENT left: closing the replica conn
                            # (caller's finally) trips the engine's
                            # SSE write failure -> abort + slot free
                            router.flight.record(
                                "stream_client_gone", rid=entry.rid)
                            return saw_done, None, True
                except (OSError, http.client.HTTPException):
                    pass                 # replica died mid-read
                return saw_done, mig, False

            def _relay(self, entry: JournalEntry, r: Replica, resp):
                """Relay the stream; when the replica hands the
                sequence off mid-decode, re-POST the journaled body to
                the migration target with X-Resume-Id and ride the
                continuation SSE on the SAME client socket — the
                client sees one uninterrupted stream whose bytes match
                an unmigrated run (the target's first delta carries
                the boundary separator; serving/api_server.py seeds
                the resumed decode state)."""
                hops = 0
                conn2 = None
                try:
                    while True:
                        saw_done, mig, gone = self._pump(entry, resp)
                        if saw_done or gone:
                            return
                        if mig is None:
                            break        # replica lost mid-stream
                        hops += 1
                        if hops > len(router.replicas) + 1:
                            break
                        resume_id = mig.get("resume_id")
                        target = str(mig.get("target") or "")
                        router._count("migration_continuations")
                        router.journal.record_migration(
                            entry.rid, resume_id, target)
                        router.flight.record(
                            "migration_continue", rid=entry.rid,
                            resume_id=resume_id, target=target,
                            stream=True)
                        if entry.trace is not None:
                            router.spans.annotate(
                                entry.trace[0], "migration_continue",
                                parent_id=entry.trace[2],
                                target=target, resume_id=resume_id,
                                request_id=entry.rid)
                        rep = router._replica_at(target)
                        if rep is None or not resume_id:
                            break
                        hdrs = router._fwd_headers(entry)
                        hdrs["X-Resume-Id"] = str(resume_id)
                        if conn2 is not None:
                            conn2.close()
                        conn2 = http.client.HTTPConnection(
                            router.host, rep.port,
                            timeout=router.cfg.connect_timeout_sec)
                        router.journal.assign(entry.rid, rep.idx,
                                              rep.generation)
                        try:
                            conn2.request("POST", entry.path,
                                          body=entry.body,
                                          headers=hdrs)
                            conn2.sock.settimeout(
                                router.cfg.forward_timeout_sec)
                            resp2 = conn2.getresponse()
                        except (OSError,
                                http.client.HTTPException):
                            break
                        if resp2.status != 200:
                            try:
                                resp2.read()
                            except (OSError,
                                    http.client.HTTPException):
                                pass
                            break
                        router._breaker_success(rep)
                        r = rep
                        resp = resp2
                    # REPLICA (or its migration continuation) lost
                    # mid-stream: structured error, not a dropped
                    # socket — generated bytes already with the client
                    # cannot be resumed transparently
                    router._count("failovers")
                    router._count("stream_errors")
                    router._c_failovers.inc()
                    router._breaker_failure(r)
                    retry = router.retry_after_hint()
                    router.flight.record("stream_replica_lost",
                                         rid=entry.rid,
                                         replica=r.idx)
                    event = {"error": {
                        "message": "replica failed mid-stream; "
                                   "resubmit the request",
                        "type": "replica_failover", "code": 503,
                        "retry_after": retry}}
                    try:
                        self.wfile.write(
                            b"data: " + json.dumps(event).encode()
                            + b"\n\ndata: [DONE]\n\n")
                        self.wfile.flush()
                    except OSError:
                        pass
                finally:
                    if conn2 is not None:
                        conn2.close()

        return Handler

    def serve(self, host: str = "127.0.0.1", port: int = 8080,
              background: bool = False) -> ThreadingHTTPServer:
        self._httpd = ThreadingHTTPServer((host, port),
                                          self.make_handler())
        if background:
            t = threading.Thread(target=self._httpd.serve_forever,
                                 daemon=True)
            t.start()
        else:
            self._httpd.serve_forever()
        return self._httpd


def main():
    """CLI: python -m bigdl_tpu.serving.router --model PATH
    --replicas N [--tiny-random] — spawns the replicas as
    ``api_server`` subprocesses and serves the routed OpenAI API."""
    import argparse
    import signal

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--load-in-low-bit", default="sym_int4")
    ap.add_argument("--tiny-random", action="store_true")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--replicas", type=int, default=None,
                    help="default $BIGDL_TPU_ROUTER_REPLICAS (2)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=2048)
    ap.add_argument("--health-sec", type=float, default=None,
                    help="default $BIGDL_TPU_ROUTER_HEALTH_SEC (1.0)")
    ap.add_argument("--hedge-ms", type=float, default=None,
                    help="default $BIGDL_TPU_ROUTER_HEDGE_MS (0 = off)")
    ap.add_argument("--crash-budget", type=int, default=None,
                    help="default $BIGDL_TPU_ROUTER_CRASH_BUDGET (3)")
    ap.add_argument("--roles", default=None,
                    help="comma-separated per-index fleet roles, e.g. "
                         "'prefill,decode' (rest default to mixed)")
    ap.add_argument("--journal", default=None,
                    help="durable JSONL request-journal path (default "
                         "$BIGDL_TPU_ROUTER_JOURNAL; unset = "
                         "in-memory only)")
    ap.add_argument("--autoscale", action="store_true",
                    help="run the load-signal autoscaler "
                         "(serving/autoscaler.py; bounds from "
                         "$BIGDL_TPU_AUTOSCALE_MIN/MAX, dwell from "
                         "$BIGDL_TPU_AUTOSCALE_DWELL_SEC)")
    args = ap.parse_args()

    if not args.model and not args.tiny_random:
        ap.error("--model is required (or pass --tiny-random)")
    roles = ([s.strip() for s in args.roles.split(",") if s.strip()]
             if args.roles else None)
    cmd = [sys.executable, "-m", "bigdl_tpu.serving.api_server",
           "--host", args.host, "--port", "{port}",
           "--max-batch", str(args.max_batch),
           "--max-seq", str(args.max_seq)]
    if args.tiny_random:
        cmd += ["--tiny-random"]
    else:
        cmd += ["--model", args.model,
                "--load-in-low-bit", args.load_in_low_bit]

    router = Router(
        replica_cmd=cmd,
        config=RouterConfig(replicas=args.replicas,
                            health_sec=args.health_sec,
                            hedge_ms=args.hedge_ms,
                            crash_budget=args.crash_budget,
                            roles=roles,
                            journal_path=args.journal),
        host=args.host)
    print(f"router: spawning {router.cfg.replicas} replicas on ports "
          f"{[r.port for r in router.replicas]}", file=sys.stderr)
    router.start()

    scaler = None
    if args.autoscale:
        from bigdl_tpu.serving.autoscaler import Autoscaler

        scaler = Autoscaler(router)
        scaler.start()
        print(f"autoscaler: bounds [{scaler.cfg.min_replicas}, "
              f"{scaler.cfg.max_replicas}], dwell "
              f"{scaler.cfg.dwell_sec}s", file=sys.stderr)

    def _term(signum, frame):
        if scaler is not None:
            scaler.stop()
        threading.Thread(target=router.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    print(f"routing on http://{args.host}:{args.port}/v1",
          file=sys.stderr)
    router.serve(args.host, args.port)


if __name__ == "__main__":
    main()
