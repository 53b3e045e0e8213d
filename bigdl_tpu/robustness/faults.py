"""Deterministic, seedable fault injection for the serving stack.

Chaos testing only exercises real recovery code when the faults land in
the real execution paths: the serving engine calls into this module at
its step / admit / prefill / logits points (serving/engine.py), and the
`Generator` step path exposes the same hook (generation.py). With no
spec configured every hook is a no-op costing one attribute check.

Spec grammar (``$BIGDL_TPU_FAULT_SPEC`` or ``parse_fault_spec()``):

    spec    := clause (';' clause)*
    clause  := kind '@' param (',' param)*
    param   := key '=' value

Kinds and the injection points they attach to:

- ``step_exception``  — raise ``InjectedFault`` from the engine's
  batched decode step (point ``"step"``). The engine's retry /
  quarantine machinery is the recovery path under test.
- ``admit_exception`` — raise from the admission bookkeeping path
  (point ``"admit"``), blaming a single identifiable request.
- ``prefill_exception`` — raise around the chunked prefill call
  (point ``"prefill"``), also request-attributable.
- ``nan_logits``      — poison one slot's logits row with NaN after the
  decode (point ``"logits"``); exercises the per-slot health check and
  quarantine. ``slot=i`` targets a fixed row (default: the lowest
  active slot).
- ``logit_drift``     — add a FINITE constant bias (``bias=``, default
  3.0) to one vocab column of every active logits row from the first
  firing onward (point ``"logits"``). Unlike ``nan_logits`` this is invisible to the
  engine's isfinite health check: the replica keeps serving at full
  speed with every gauge green, but greedy argmax changes — silent
  correctness drift. The detection path under test is the router's
  golden-canary probes (serving/canary.py), which quarantine the
  replica on byte mismatch. Once fired, drift stays on for the life of
  the process (real corruption doesn't heal); ``times=`` caps only the
  number of *onset* firings.
- ``slow_step``       — sleep ``ms=`` milliseconds at the step point;
  exercises deadline enforcement without a slow model.
- ``replica_crash``   — hard-kill THIS PROCESS (``os._exit``, default
  code 137 — indistinguishable from an external ``kill -9``) at the
  step point. The engine only consults this kind on steps with live
  work, so the crash lands MID-REQUEST (``every=N`` counts busy steps).
  The recovery path under test lives one level up: the serving
  router's supervisor, failover, and request replay
  (serving/router.py). ``code=`` overrides the exit code.
- ``replica_hang``    — freeze the engine's step loop at the step
  point (sleep ``ms=`` milliseconds, or forever when unset). The
  process stays alive and its HTTP threads keep answering, so this
  exercises wedge detection (`/health` heartbeat) and the router's
  hang-kill-restart path rather than crash handling.
- ``overload_storm``  — force the engine's brownout pressure signal to
  ``pressure=`` (default 1.0) on steps where the clause fires (point
  ``"storm"``), driving the overload ladder (serving/overload.py)
  deterministically without real traffic: brownout escalation, QoS
  shedding, and hysteresis recovery all become scriptable.
- ``handoff_drop``    — make the prefill replica's KV-handoff POST to a
  decode replica fail as if the wire dropped it (point ``"handoff"``,
  consulted via ``drop_point`` before each transfer attempt; every
  attempt counts as one visit, so ``every=N`` drops every Nth
  attempt). The recovery path under test is the handoff
  retry/backoff ladder and the local-decode fallback
  (serving/api_server.py) — the request must never be lost.
- ``scale_flap``      — force the fleet autoscaler to alternate
  scale-up/scale-down decisions on ticks where the clause fires
  (``flap_direction``), bypassing its dwell/hysteresis gating. The
  invariants under test are the hard guards: never retire the last
  healthy replica, never fight a rolling restart
  (serving/autoscaler.py).
- ``migration_drop``  — make a live-migration transfer attempt fail at
  one of its three gates (``gate=send|recv|commit``; unset = every
  gate): ``send`` fails the POST before any bytes leave the source,
  ``recv`` makes the target reject before staging, ``commit`` stages
  the state on the target but loses the ACK (the crash-after-commit
  matrix row). Consulted via ``drop_point("migrate_<gate>")``. The
  recovery path under test is the source's local-resume fallback and
  the router's journal replay — the request must never be lost.
- ``migration_corrupt`` — flip one bit in a framed internal wire
  payload after checksumming (``corrupt_point``; ``point=`` scopes to
  ``migrate`` or ``handoff``, unset = both). The receiver's CRC32
  check must reject it with a structured 400 counted in
  ``bigdl_tpu_handoff_rejects_total{reason="crc"}``.
- ``net_latency``     — add ``ms=`` milliseconds of latency to
  fleet-internal HTTP client calls (router→replica stats/canary
  probes and admin fan-outs, replica→replica handoff/migrate posts).
  ``point=`` scopes to one path (``handoff``, ``migrate``, ``stats``,
  ``canary``, ``admin``); unset applies to all internal calls.
- ``net_drop``        — fail fleet-internal HTTP client calls as if
  the connection reset (``p=`` per-call probability, or the usual
  every/times triggers). Same ``point=`` scoping as ``net_latency``.
  Together they make migration/handoff timeout+retry paths
  chaos-testable deterministically.

Trigger params (every kind):

- ``p=0.05``        — fire with probability p per visit (seeded; see
  ``seed=``). Deterministic given the seed and visit order.
- ``after_step=N``  — fire at the first visit whose ``step >= N``.
- ``at_step=N``     — fire at visits with ``step == N`` exactly.
- ``every=N``       — fire every Nth visit to the point (1 = always).
- ``times=K``       — total-fire cap (default 1 for ``after_step`` /
  ``at_step``, unlimited otherwise; ``times=0`` means unlimited).
- ``seed=S``        — seed for this clause's RNG (default 0): two runs
  with the same spec inject the identical fault sequence.
- ``ms=M``          — sleep milliseconds (``slow_step``; for
  ``replica_hang`` a bounded freeze instead of forever).
- ``slot=i``        — target row (``nan_logits`` only).
- ``code=C``        — process exit code (``replica_crash`` only).
- ``pressure=P``    — forced brownout pressure in [0, 1]
  (``overload_storm`` only; default 1.0).
- ``bias=B``        — additive logit bias (``logit_drift`` only;
  default 3.0; must be finite and non-zero).
- ``gate=G``        — migration gate to fail (``migration_drop``
  only): ``send``, ``recv``, or ``commit``; unset fires at every gate.
- ``point=P``       — internal-HTTP path scope (``net_latency`` /
  ``net_drop`` / ``migration_corrupt``); unset applies everywhere the
  hook is consulted.

Example: ``step_exception@p=0.05,seed=7;slow_step@ms=500,every=10``.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional

import numpy as np

FAULT_SPEC_ENV = "BIGDL_TPU_FAULT_SPEC"

KINDS = ("step_exception", "admit_exception", "prefill_exception",
         "nan_logits", "logit_drift", "slow_step", "replica_crash",
         "replica_hang", "overload_storm", "handoff_drop", "scale_flap",
         "migration_drop", "migration_corrupt", "net_latency",
         "net_drop")

#: live-migration transfer gates migration_drop can target
MIGRATION_GATES = ("send", "recv", "commit")

#: default exit code for replica_crash — what an external ``kill -9``
#: surfaces as through the shell (128 + SIGKILL)
CRASH_EXIT_CODE = 137

# injection point -> exception kinds that fire there
_RAISE_POINTS = {
    "step": "step_exception",
    "admit": "admit_exception",
    "prefill": "prefill_exception",
}

_INT_PARAMS = ("after_step", "at_step", "every", "times", "seed", "slot",
               "code")
_FLOAT_PARAMS = ("p", "ms", "pressure", "bias")
_STR_PARAMS = ("gate", "point")


class InjectedFault(RuntimeError):
    """A fault raised by the injection harness. ``transient`` mirrors
    what the recovery code assumes about real-world analogs (XLA
    transfer hiccups, runtime resets): retrying may succeed."""

    def __init__(self, kind: str, point: str, step: int):
        super().__init__(f"injected {kind} at {point} (step {step})")
        self.kind = kind
        self.point = point
        self.step = step
        self.transient = True


@dataclasses.dataclass
class FaultClause:
    kind: str
    p: float = 0.0
    after_step: Optional[int] = None
    at_step: Optional[int] = None
    every: int = 0
    times: Optional[int] = None       # None = unlimited
    seed: int = 0
    ms: float = 0.0
    slot: Optional[int] = None
    code: Optional[int] = None        # replica_crash exit code
    pressure: float = 1.0             # overload_storm forced pressure
    bias: float = 3.0                 # logit_drift additive bias
    gate: Optional[str] = None        # migration_drop target gate
    point: Optional[str] = None       # net_* / migration_corrupt scope
    # runtime state
    fired: int = 0
    visits: int = 0
    _rng: Optional[np.random.Generator] = None

    def __post_init__(self):
        if self.times is None and (self.after_step is not None
                                   or self.at_step is not None):
            self.times = 1            # one-shot by default for step pins
        if self.times == 0:
            self.times = None
        self._rng = np.random.default_rng(self.seed)

    def should_fire(self, step: int) -> bool:
        self.visits += 1
        if self.times is not None and self.fired >= self.times:
            return False
        hit = False
        if self.at_step is not None:
            hit = step == self.at_step
        elif self.after_step is not None:
            hit = step >= self.after_step
        elif self.every > 0:
            hit = self.visits % self.every == 0
        elif self.p > 0.0:
            hit = bool(self._rng.random() < self.p)
        if hit:
            self.fired += 1
        return hit


def parse_fault_spec(spec: str) -> List[FaultClause]:
    """Parse a fault spec string; raises ``ValueError`` on malformed
    input (unknown kind, bad param, non-numeric value)."""
    clauses: List[FaultClause] = []
    for raw in (spec or "").split(";"):
        raw = raw.strip()
        if not raw:
            continue
        kind, _, params = raw.partition("@")
        kind = kind.strip()
        if kind not in KINDS:
            raise ValueError(
                f"unknown fault kind {kind!r} (choices: {', '.join(KINDS)})")
        kw: Dict[str, object] = {}
        for pair in params.split(","):
            pair = pair.strip()
            if not pair:
                continue
            key, sep, val = pair.partition("=")
            key = key.strip()
            if not sep:
                raise ValueError(f"fault param {pair!r} is not key=value")
            try:
                if key in _INT_PARAMS:
                    kw[key] = int(val)
                elif key in _FLOAT_PARAMS:
                    kw[key] = float(val)
                elif key in _STR_PARAMS:
                    kw[key] = val.strip()
                else:
                    raise ValueError(
                        f"unknown fault param {key!r} for {kind!r}")
            except ValueError as e:
                if "unknown fault param" in str(e):
                    raise
                raise ValueError(
                    f"fault param {key!r}={val!r} is not numeric") from None
        if kw.get("p", 0.0) and not (0.0 < kw["p"] <= 1.0):  # type: ignore
            raise ValueError(f"fault probability p={kw['p']} not in (0, 1]")
        pr = kw.get("pressure")
        if pr is not None and not (0.0 <= pr <= 1.0):  # type: ignore
            raise ValueError(
                f"overload_storm pressure={pr} not in [0, 1]")
        b = kw.get("bias")
        if b is not None and (b != b or b in (float("inf"),
                                              float("-inf")) or b == 0.0):
            raise ValueError(
                f"logit_drift bias={b} must be finite and non-zero")
        g = kw.get("gate")
        if g is not None and g not in MIGRATION_GATES:
            raise ValueError(
                f"migration gate {g!r} not one of "
                f"{', '.join(MIGRATION_GATES)}")
        clauses.append(FaultClause(kind=kind, **kw))  # type: ignore[arg-type]
    return clauses


def validate_fault_spec(spec: str) -> dict:
    """env_check report for ``$BIGDL_TPU_FAULT_SPEC``: parsed clause
    kinds, or the parse error."""
    try:
        clauses = parse_fault_spec(spec)
    except ValueError as e:
        return {"value": spec, "valid": False, "error": str(e)}
    return {"value": spec, "valid": True,
            "clauses": [c.kind for c in clauses]}


class FaultInjector:
    """Holds the parsed clauses and answers the engine's hook calls.

    ``NULL`` (the no-clause injector) is what engines get when no spec
    is configured — every hook is a cheap early return. ``on_fire`` is
    an optional callback ``(kind, point, step)`` the engine uses to
    count ``bigdl_tpu_faults_injected_total`` and drop a flight event.
    """

    def __init__(self, clauses: Optional[List[FaultClause]] = None,
                 on_fire=None):
        self.clauses = clauses or []
        self.on_fire = on_fire
        self._by_kind: Dict[str, List[FaultClause]] = {}
        for c in self.clauses:
            self._by_kind.setdefault(c.kind, []).append(c)

    @classmethod
    def from_env(cls, env: Optional[str] = None) -> "FaultInjector":
        spec = env if env is not None else os.environ.get(
            FAULT_SPEC_ENV, "")
        return cls(parse_fault_spec(spec)) if spec else cls()

    @property
    def enabled(self) -> bool:
        return bool(self.clauses)

    def _fired(self, kind: str, point: str, step: int) -> None:
        if self.on_fire is not None:
            try:
                self.on_fire(kind, point, step)
            except Exception:
                pass                  # telemetry must not alter the fault

    def raise_point(self, point: str, step: int) -> None:
        """Raise ``InjectedFault`` when a clause of the point's
        exception kind fires. Engine calls this at step/admit/prefill."""
        if not self.clauses:
            return
        kind = _RAISE_POINTS.get(point)
        if kind is None:
            return
        for c in self._by_kind.get(kind, ()):
            if c.should_fire(step):
                self._fired(kind, point, step)
                raise InjectedFault(kind, point, step)

    def process_point(self, point: str, step: int) -> None:
        """Process-granularity faults for the multi-replica chaos
        harness (serving/router.py). A firing ``replica_crash`` clause
        hard-kills this process with ``os._exit`` (no atexit, no flush
        — the same hole an OOM-kill or ``kill -9`` leaves); a firing
        ``replica_hang`` clause blocks this thread for ``ms``
        milliseconds (forever when unset), freezing the engine's step
        loop while the process stays alive. Engine calls this at the
        step point only."""
        if not self.clauses or point != "step":
            return
        for c in self._by_kind.get("replica_crash", ()):
            if c.should_fire(step):
                self._fired("replica_crash", point, step)
                os._exit(c.code if c.code is not None else CRASH_EXIT_CODE)
        for c in self._by_kind.get("replica_hang", ()):
            if c.should_fire(step):
                self._fired("replica_hang", point, step)
                if c.ms > 0:
                    time.sleep(c.ms / 1000.0)
                else:
                    while True:       # until the supervisor kills us
                        time.sleep(60.0)

    def sleep_ms(self, point: str, step: int) -> float:
        """Milliseconds the caller should sleep at this point (0 when
        no slow_step clause fires). The caller sleeps — the injector
        never blocks on its own."""
        if not self.clauses or point != "step":
            return 0.0
        total = 0.0
        for c in self._by_kind.get("slow_step", ()):
            if c.should_fire(step):
                self._fired("slow_step", point, step)
                total += c.ms
        return total

    def storm_pressure(self, step: int) -> Optional[float]:
        """Forced brownout pressure for this step, or None when no
        ``overload_storm`` clause fires. Multiple firing clauses take
        the max. The engine feeds the result into its overload
        controller IN PLACE OF the measured pressure floor, so a chaos
        test drives the full brownout ladder without real load."""
        if not self.clauses:
            return None
        forced: Optional[float] = None
        for c in self._by_kind.get("overload_storm", ()):
            if c.should_fire(step):
                self._fired("overload_storm", "storm", step)
                forced = c.pressure if forced is None \
                    else max(forced, c.pressure)
        return forced

    def drop_point(self, point: str, step: int) -> bool:
        """True when a drop clause fires at this point — the caller
        must treat the in-flight transfer attempt as lost (no bytes
        delivered) and run its retry/fallback ladder. Each attempt is
        one visit. ``"handoff"`` consults ``handoff_drop``;
        ``"migrate_send"`` / ``"migrate_recv"`` / ``"migrate_commit"``
        consult ``migration_drop`` clauses whose ``gate`` matches the
        suffix (a gate-less clause fires at every migration gate)."""
        if not self.clauses:
            return False
        dropped = False
        if point == "handoff":
            for c in self._by_kind.get("handoff_drop", ()):
                if c.should_fire(step):
                    self._fired("handoff_drop", point, step)
                    dropped = True
        elif point.startswith("migrate_"):
            gate = point[len("migrate_"):]
            for c in self._by_kind.get("migration_drop", ()):
                if c.gate is not None and c.gate != gate:
                    continue
                if c.should_fire(step):
                    self._fired("migration_drop", point, step)
                    dropped = True
        return dropped

    def corrupt_point(self, point: str, step: int) -> bool:
        """True when a ``migration_corrupt`` clause fires: the sender
        must flip a bit in its already-checksummed frame
        (serving/wire.corrupt_frame) before the POST, so the receiver's
        CRC32 rejection path is what gets exercised. ``point`` is
        ``"migrate"`` or ``"handoff"``; a clause's ``point=`` scopes
        it, unset fires at both."""
        if not self.clauses:
            return False
        corrupted = False
        for c in self._by_kind.get("migration_corrupt", ()):
            if c.point is not None and c.point != point:
                continue
            if c.should_fire(step):
                self._fired("migration_corrupt", point, step)
                corrupted = True
        return corrupted

    def net_delay_ms(self, point: str, step: int = 0) -> float:
        """Milliseconds of injected latency for one fleet-internal
        HTTP client call at ``point`` (0 when no scoped ``net_latency``
        clause fires). The caller sleeps before issuing the call."""
        if not self.clauses:
            return 0.0
        total = 0.0
        for c in self._by_kind.get("net_latency", ()):
            if c.point is not None and c.point != point:
                continue
            if c.should_fire(step):
                self._fired("net_latency", point, step)
                total += c.ms
        return total

    def net_dropped(self, point: str, step: int = 0) -> bool:
        """True when a scoped ``net_drop`` clause fires: the caller
        must fail this fleet-internal HTTP call as if the connection
        reset (raise ``OSError`` before any bytes move)."""
        if not self.clauses:
            return False
        dropped = False
        for c in self._by_kind.get("net_drop", ()):
            if c.point is not None and c.point != point:
                continue
            if c.should_fire(step):
                self._fired("net_drop", point, step)
                dropped = True
        return dropped

    def flap_direction(self, step: int) -> Optional[str]:
        """Forced autoscaler decision for this tick: ``"up"``, ``"down"``
        (alternating per firing, starting with "up"), or None when no
        ``scale_flap`` clause fires. The autoscaler applies the forced
        direction INSTEAD OF its dwell/hysteresis-gated decision — its
        hard guards (min/max replica bounds, last-healthy, rolling
        restart exclusion) still apply and are exactly what a flap
        chaos test exercises."""
        if not self.clauses:
            return None
        direction: Optional[str] = None
        for c in self._by_kind.get("scale_flap", ()):
            if c.should_fire(step):
                self._fired("scale_flap", "scale", step)
                # c.fired was just incremented: odd firings go up,
                # even firings go down — a deterministic flap
                direction = "up" if c.fired % 2 == 1 else "down"
        return direction

    def drift_rows(self, step: int, active_rows):
        """``(rows, bias)`` — logits rows to shift by a finite additive
        ``bias`` this step (``([], 0.0)`` when no ``logit_drift``
        clause is live). Drift is STICKY: once a clause fires its bias
        applies to every active row on every later step, modelling
        corruption that doesn't heal. The shifted logits stay finite,
        so the engine's isfinite health check passes and only a golden
        canary replay can notice."""
        if not self.clauses or not active_rows:
            return [], 0.0
        bias = 0.0
        for c in self._by_kind.get("logit_drift", ()):
            if getattr(c, "_drifting", False):
                bias += c.bias
            elif c.should_fire(step):
                self._fired("logit_drift", "logits", step)
                c._drifting = True    # type: ignore[attr-defined]
                bias += c.bias
        if bias == 0.0:
            return [], 0.0
        return list(active_rows), bias

    def poison_rows(self, step: int, active_rows) -> List[int]:
        """Rows of the decode logits to overwrite with NaN this step
        (empty when no nan_logits clause fires). A clause with
        ``slot=i`` targets that row if it is active; otherwise the
        lowest active row is poisoned."""
        if not self.clauses or not active_rows:
            return []
        rows: List[int] = []
        for c in self._by_kind.get("nan_logits", ()):
            if c.should_fire(step):
                row = c.slot if (c.slot is not None
                                 and c.slot in active_rows) \
                    else active_rows[0]
                self._fired("nan_logits", "logits", step)
                rows.append(row)
        return rows


#: shared no-op injector for unconfigured engines
NULL = FaultInjector()
