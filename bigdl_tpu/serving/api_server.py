"""OpenAI-compatible HTTP server over the continuous-batching engine.

Equivalent of the reference's FastAPI server (reference
vllm/entrypoints/openai/api_server.py:229-425: /v1/completions and
/v1/chat/completions with SSE streaming, client-disconnect abort) — built on
the stdlib ThreadingHTTPServer so it runs with zero extra dependencies
(FastAPI/uvicorn are not in the image; the engine below is framework-
agnostic regardless).

Endpoints: GET /v1/models, POST /v1/completions, POST /v1/chat/completions
(stream=true -> text/event-stream chunks, OpenAI wire format), and
POST /v1/embeddings when constructed with an embedder (BertEmbedder).

Observability endpoints (bigdl_tpu/observability/):
- GET /metrics — Prometheus text exposition of the engine's registry
- GET /v1/stats — JSON engine snapshot (slots, queues, metric
  summaries, recent request spans, jit compile table)
- GET /v1/memory — HBM memory snapshot (ledger static report, live
  device memory_stats when the backend has them, budget/headroom math
  and the engine's admission-deferral accounting)
- GET /v1/debug/dump — on-demand postmortem JSON (flight-recorder
  tail, span tail, metrics snapshot, compile table, config + env
  fingerprint); the same document the engine writes to
  $BIGDL_TPU_POSTMORTEM_DIR on step exceptions, stall-guard trips,
  and (via the CLI's signal hooks) SIGTERM/SIGINT
- GET /v1/internal/spans?trace_id= — completed distributed-trace spans
  for one trace (observability/disttrace.py), stamped with this
  replica's wall clock; the router's GET /v1/trace/{id} fan-out target
- POST /v1/profiler/start {"log_dir": ...} / POST /v1/profiler/stop —
  on-demand jax.profiler device trace against the live server
  (TensorBoard/Perfetto; wraps utils/profiling.start_profiler)
- GET /v1/profiler/status — whether a capture is running, and where
- GET /v1/slo — per-replica SLO state: resolved spec, burn rates per
  (qos, objective, window), active alerts (observability/slo.py)
- GET /v1/usage — per-tenant usage rollup: totals + current token
  burn from the usage ledger (observability/usage.py)

Tokenization: pass a HF tokenizer (transformers.AutoTokenizer) at
construction; prompts may also be raw token-id lists, in which case
completions return token ids (useful for tests and token-level clients).
"""

from __future__ import annotations

import base64
import dataclasses
import hashlib
import json
import os
import select
import socket
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, List, Optional

import numpy as np

from bigdl_tpu.observability.compile_watch import (compiles_in_progress,
                                                   startup_snapshot)
from bigdl_tpu.observability.compile_watch import mark as startup_mark
from bigdl_tpu.observability.disttrace import (make_traceparent,
                                               new_span_id,
                                               parse_traceparent)
from bigdl_tpu.serving.engine import (EngineDraining, LLMEngine,
                                      SamplingParams)
from bigdl_tpu.serving.overload import RequestShed
from bigdl_tpu.serving.wire import (REJECT_REASONS, WireError,
                                    corrupt_frame, frame_payload,
                                    is_framed, unframe_payload)
from bigdl_tpu.utils.profiling import annotate

#: engine finish reasons that map to HTTP 504 (the request ran out of
#: time: its own deadline, or the server's drain window closed on it)
_TIMEOUT_REASONS = ("deadline", "drain_timeout")

#: replica roles in the disaggregated fleet (serving/router.py,
#: serving/autoscaler.py): a ``prefill`` replica runs chunked prefill
#: and ships the prompt's quantized KV snapshot to a ``decode`` replica
#: over POST /v1/internal/kv_handoff; ``mixed`` does both locally
REPLICA_ROLES = ("mixed", "prefill", "decode")


def resolve_replica_role(value: Optional[str] = None) -> str:
    """$BIGDL_TPU_REPLICA_ROLE (default "mixed"); raises ValueError on
    an unknown role."""
    v = value if value is not None else os.environ.get(
        "BIGDL_TPU_REPLICA_ROLE", "mixed")
    v = (v or "mixed").strip().lower()
    if v not in REPLICA_ROLES:
        raise ValueError(f"replica role {v!r} not one of "
                         f"{', '.join(REPLICA_ROLES)}")
    return v


def resolve_handoff_timeout_ms(value: Optional[float] = None) -> float:
    """$BIGDL_TPU_HANDOFF_TIMEOUT_MS (default 5000): per-attempt wall
    budget for one KV-handoff POST to a decode replica."""
    if value is not None:
        v = float(value)
    else:
        v = float(os.environ.get("BIGDL_TPU_HANDOFF_TIMEOUT_MS", "5000"))
    if v <= 0:
        raise ValueError(f"handoff timeout {v} ms must be > 0")
    return v


def resolve_handoff_retries(value: Optional[int] = None) -> int:
    """$BIGDL_TPU_HANDOFF_RETRIES (default 2): transfer attempts beyond
    the first before falling back to local mixed decode."""
    if value is not None:
        v = int(value)
    else:
        v = int(os.environ.get("BIGDL_TPU_HANDOFF_RETRIES", "2"))
    if v < 0:
        raise ValueError(f"handoff retries {v} must be >= 0")
    return v


#: tristate values for $BIGDL_TPU_LIVE_MIGRATION ("auto" == enabled:
#: the knob exists so operators can hard-disable migration fleetwide,
#: and so a future build can gate "auto" on measured link bandwidth
#: without breaking explicit opt-ins)
LIVE_MIGRATION_MODES = ("auto", "on", "off")


def resolve_live_migration(value: Optional[str] = None) -> str:
    """$BIGDL_TPU_LIVE_MIGRATION (default "auto"): whether this replica
    accepts /v1/internal/migrate_in intakes and runs migrate-out on
    planned disruptions. Raises ValueError on an unknown mode."""
    v = value if value is not None else os.environ.get(
        "BIGDL_TPU_LIVE_MIGRATION", "auto")
    v = (v or "auto").strip().lower()
    if v not in LIVE_MIGRATION_MODES:
        raise ValueError(f"live migration mode {v!r} not one of "
                         f"{', '.join(LIVE_MIGRATION_MODES)}")
    return v


def resolve_migrate_timeout_ms(value: Optional[float] = None) -> float:
    """$BIGDL_TPU_MIGRATE_TIMEOUT_MS (default 5000): wall budget for
    one sequence export AND for each migrate_in POST attempt."""
    if value is not None:
        v = float(value)
    else:
        v = float(os.environ.get("BIGDL_TPU_MIGRATE_TIMEOUT_MS", "5000"))
    if v <= 0:
        raise ValueError(f"migrate timeout {v} ms must be > 0")
    return v


def resolve_migrate_max_bytes(value: Optional[int] = None) -> int:
    """$BIGDL_TPU_MIGRATE_MAX_BYTES (default 64 MiB): largest framed
    migration payload either side will move — a sender whose export
    exceeds it resumes locally, a receiver rejects oversized intakes
    with reason "too_large" before reading the body."""
    if value is not None:
        v = int(value)
    else:
        v = int(os.environ.get("BIGDL_TPU_MIGRATE_MAX_BYTES",
                               str(64 << 20)))
    if v <= 0:
        raise ValueError(f"migrate max bytes {v} must be > 0")
    return v


def _np_dtype(name: str):
    """np.dtype by name, falling back to the ml_dtypes extension types
    (bfloat16, float8_e5m2, ...) the KV planes are stored in."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def planes_to_wire(entry) -> List[dict]:
    """KV snapshot planes -> JSON-able wire form. Each plane rides as
    raw bytes (base64) + dtype/shape, so int8/int4-quantized planes
    ship at their quantized width (~1/4 of bf16 for int4+scales) —
    exactly the prefix-cache entry layout, (k, v[, k_scale, v_scale])."""
    out = []
    for p in entry:
        p = np.ascontiguousarray(p)
        out.append({"dtype": p.dtype.name, "shape": list(p.shape),
                    "data": base64.b64encode(p.tobytes()).decode("ascii")})
    return out


def planes_from_wire(objs: List[dict]):
    """Inverse of planes_to_wire; raises ValueError on a malformed or
    truncated plane."""
    if not isinstance(objs, list) or not 2 <= len(objs) <= 4:
        raise ValueError("planes must be a list of 2-4 plane objects")
    entry = []
    for o in objs:
        if not isinstance(o, dict):
            raise ValueError("each plane must be an object")
        try:
            dt = _np_dtype(str(o["dtype"]))
            shape = tuple(int(s) for s in o["shape"])
            raw = base64.b64decode(o["data"])
        except (KeyError, TypeError, ValueError, AttributeError) as e:
            raise ValueError(f"malformed KV plane: {e}") from None
        arr = np.frombuffer(raw, dtype=dt)
        if arr.size != int(np.prod(shape)):
            raise ValueError(
                f"plane byte count {arr.size} != shape {shape}")
        entry.append(arr.reshape(shape).copy())
    return tuple(entry)


def _socket_disconnected(sock) -> bool:
    """True when the client peer has closed its end (readable socket
    whose MSG_PEEK returns EOF). Used to cancel NON-streaming requests
    — the streaming path learns the same thing from its write failing."""
    try:
        r, _, _ = select.select([sock], [], [], 0)
        if not r:
            return False
        return sock.recv(1, socket.MSG_PEEK | socket.MSG_DONTWAIT) == b""
    except (BlockingIOError, InterruptedError):
        return False
    except OSError:
        return True


class _EngineLoop:
    """Background thread driving engine.step() (the reference's asyncio
    engine loop, async_llm_engine.py, minus asyncio)."""

    def __init__(self, engine: LLMEngine):
        self.engine = engine
        self._wake = threading.Event()
        self._stop = False
        # where the engine thread's time goes, over the whole run: the
        # loop's share without work tells "no work" from "host busy"
        seconds = engine.registry.counter(
            "bigdl_tpu_engine_loop_seconds_total",
            "Engine thread wall time: state=step inside engine.step(), "
            "state=wait blocked for work after a step that did none.",
            labelnames=("state",))
        self._m_step_s = seconds.labels("step")
        self._m_wait_s = seconds.labels("wait")
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop:
            t0 = time.perf_counter()
            try:
                did = self.engine.step()
            except Exception:   # a dead loop thread would hang every client
                import traceback

                traceback.print_exc()
                did = False
            t1 = time.perf_counter()
            self._m_step_s.inc(t1 - t0)
            if not did:
                # a span of its own, so that a profiler capture tells
                # "no work" from "host busy" on the engine thread
                with annotate("engine_loop.wait"):
                    self._wake.wait(timeout=0.01)
                self._wake.clear()
                self._m_wait_s.inc(time.perf_counter() - t1)

    def notify(self):
        self._wake.set()

    def stop(self):
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=2)


def _jsonable(obj):
    """Round-trip through JSON with repr() fallback — the postmortem
    dict may carry values json.dumps can't encode natively (the same
    default=repr the on-disk dump writer uses)."""
    return json.loads(json.dumps(obj, default=repr))


def _chat_to_prompt(messages: List[dict], tokenizer) -> Any:
    if tokenizer is not None and hasattr(tokenizer, "apply_chat_template"):
        try:
            return tokenizer.apply_chat_template(
                messages, tokenize=True, add_generation_prompt=True)
        except Exception:
            pass
    text = ""
    for m in messages:
        text += f"{m.get('role', 'user')}: {m.get('content', '')}\n"
    text += "assistant:"
    return text


class _IncrementalDetok:
    """vllm-style incremental detokenization (reference: vllm's
    Detokenizer; replaces the accumulated-decode diff flagged in r4
    advice). Each delta is computed from a sliding token window
    (`decode(ids[prefix:])` minus `decode(ids[prefix:read])`), so the
    stream is append-only BY CONSTRUCTION even when a full re-decode
    would retroactively rewrite earlier text (sentencepiece boundary
    cleanup, clean_up_tokenization_spaces), and total work is O(n) in
    generation length rather than O(n^2)."""

    def __init__(self, decode_fn):
        self._decode = decode_fn
        self.ids: list = []
        self.text = ""       # stable decoded text (what stop-scan sees)
        self._prefix = 0     # window start (token index)
        self._read = 0       # tokens already folded into .text

    def push(self, new_ids) -> str:
        self.ids.extend(new_ids)
        prefix_text = self._decode(self.ids[self._prefix:self._read])
        new_text = self._decode(self.ids[self._prefix:])
        if new_text.endswith("�"):
            return ""        # incomplete multi-byte char: hold the tail
        if len(new_text) <= len(prefix_text):
            return ""        # window shrank (cleanup): wait for more
        delta = new_text[len(prefix_text):]
        self._prefix = self._read
        self._read = len(self.ids)
        self.text += delta
        return delta

    def flush(self) -> str:
        """Final drain: emit the held-back tail even if it ends in
        U+FFFD — a completion may genuinely end mid-sequence, and the
        streamed text must equal the non-streaming response."""
        prefix_text = self._decode(self.ids[self._prefix:self._read])
        new_text = self._decode(self.ids[self._prefix:])
        delta = new_text[len(prefix_text):]
        self._prefix = self._read = len(self.ids)
        self.text += delta
        return delta


class OpenAIServer:
    def __init__(self, engine: LLMEngine, tokenizer=None,
                 model_name: str = "bigdl-tpu-model",
                 embedder=None, embedder_tokenizer=None,
                 wedge_sec: float = 10.0,
                 role: Optional[str] = None,
                 handoff_timeout_ms: Optional[float] = None,
                 handoff_retries: Optional[int] = None,
                 migrate_timeout_ms: Optional[float] = None,
                 migrate_max_bytes: Optional[int] = None,
                 live_migration: Optional[str] = None):
        self.engine = engine
        self.tokenizer = tokenizer
        self.model_name = model_name
        # disaggregated-serving role: "prefill" replicas ship each
        # non-streaming request's KV snapshot to a decode replica
        # (X-Handoff-Targets, set by the router) instead of decoding
        # locally; "decode" replicas accept those snapshots on
        # /v1/internal/kv_handoff; "mixed" (the default) does both.
        # None resolves $BIGDL_TPU_REPLICA_ROLE.
        self.role = resolve_replica_role(role)
        self._handoff_timeout_ms = resolve_handoff_timeout_ms(
            handoff_timeout_ms)
        self._handoff_retries = resolve_handoff_retries(handoff_retries)
        # handoff accounting, shared between HTTP handler threads and
        # /v1/stats readers — every touch goes through _handoff_lock
        self._handoff_lock = threading.Lock()
        self._handoff_counts = {"sends": 0, "accepted": 0, "retries": 0,
                                "fallbacks": 0, "dropped": 0}
        self._handoff_attempts = 0
        # live-migration knobs (serving/wire.py framing + engine
        # export/import): "off" disables both the migrate_in intake and
        # every migrate-out path — callers then fall back to
        # drain-and-replay, exactly the pre-migration behavior
        self.live_migration = resolve_live_migration(live_migration)
        self._migrate_timeout_ms = resolve_migrate_timeout_ms(
            migrate_timeout_ms)
        self._migrate_max_bytes = resolve_migrate_max_bytes(
            migrate_max_bytes)
        # rid -> {"resume_id", "target"} set by the migrate-out sender
        # at commit, popped by the HTTP handler when it emits the
        # client-facing resume marker (lock: _handoff_lock)
        self._migrated_info: dict = {}
        # wire-frame rejects at receive (magic/version/length/crc/json/
        # too_large), mirrored into /v1/stats for the router's deltas
        self._reject_counts = {r: 0 for r in REJECT_REASONS}
        self._m_rejects = engine.registry.counter(
            "bigdl_tpu_handoff_rejects_total",
            "internal wire payloads rejected at receive, by "
            "frame-validation reason",
            ["reason"])
        for r in REJECT_REASONS:
            self._m_rejects.labels(r)
        self._m_handoff = {
            key: engine.registry.counter(
                f"bigdl_tpu_handoff_{key}_total", desc)
            for key, desc in (
                ("sends", "KV handoffs delivered to a decode replica."),
                ("accepted", "KV handoffs accepted from a prefill "
                             "replica."),
                ("retries", "KV handoff attempts that failed and were "
                            "retried."),
                ("fallbacks", "KV handoffs abandoned after retries; "
                              "request decoded locally."),
                ("dropped", "KV handoff attempts dropped by the "
                            "handoff_drop chaos fault."),
            )}
        # a traced handoff whose decode target never echoed its child
        # span id (X-Trace-Span): the decode leg of the timeline is
        # missing — the span-propagation analog of a lost transfer
        self._m_span_orphans = engine.registry.counter(
            "bigdl_tpu_handoff_span_orphans_total",
            "traced KV handoffs whose decode target never reported "
            "its child span")
        # /health liveness: with unfinished work and no step() entered
        # for this long, the step loop is wedged (hung transfer,
        # replica_hang fault) — report 503 so a supervisor (the
        # serving router, k8s) kills and replaces this replica instead
        # of routing into a black hole
        self.wedge_sec = wedge_sec
        # client-disconnect cancellations by path: the streaming leg
        # learns about a dead client from its SSE write failing, the
        # non-streaming leg from the MSG_PEEK poll
        self._cancelled = engine.registry.counter(
            "bigdl_tpu_requests_cancelled_total",
            "requests aborted because the client disconnected",
            ["path"])
        # the engine-to-wire leg: a token's way from the engine's
        # _push_output to its SSE chunk written and flushed (the
        # handler's 2 ms output poll, the GIL, detokenization, the
        # socket write), and a request's way from its request line to
        # the engine's queue
        self._m_stream_delivery = engine.registry.histogram(
            "bigdl_tpu_stream_delivery_seconds",
            "Streaming: engine output pushed to its SSE chunk written "
            "and flushed, by the oldest output each chunk carries.")
        self._m_ingest = engine.registry.histogram(   # the engine's family
            "bigdl_tpu_request_phase_seconds",
            labelnames=("phase",)).labels("ingest")
        # optional /v1/embeddings backend: a BertEmbedder (transformers/
        # embedder.py) served next to the LLM — the reference serves
        # embeddings through its langchain wrapper and FastChat worker;
        # here they ride the same OpenAI-compatible server
        self.embedder = embedder
        self.embedder_tokenizer = embedder_tokenizer
        self.loop = _EngineLoop(engine)
        self._httpd: Optional[ThreadingHTTPServer] = None

    # -- request handling ---------------------------------------------------

    def _encode(self, prompt) -> List[int]:
        if isinstance(prompt, list) and prompt and isinstance(prompt[0], int):
            return list(prompt)
        if self.tokenizer is None:
            raise ValueError("string prompts need a tokenizer; pass token "
                             "ids or construct the server with one")
        return list(self.tokenizer(prompt)["input_ids"])

    def _decode_text(self, ids: List[int]) -> str:
        if self.tokenizer is None:
            # space-joined, not JSON: streaming diffs the ACCUMULATED
            # decode, so the fallback text must be append-only as ids
            # grow (a JSON list rewrites its closing bracket)
            return " ".join(str(i) for i in ids)
        return self.tokenizer.decode(ids, skip_special_tokens=True)

    def _params(self, body: dict) -> SamplingParams:
        lp = body.get("logprobs")
        if lp is True:                      # chat-style boolean form
            lp = int(body.get("top_logprobs", 0))
        return SamplingParams(
            max_tokens=int(body.get("max_tokens", 128)),
            temperature=float(body.get("temperature", 0.0)),
            top_k=int(body.get("top_k", 0)),
            top_p=float(body.get("top_p", 1.0)),
            repetition_penalty=float(body.get("repetition_penalty", 1.0)),
            presence_penalty=float(body.get("presence_penalty", 0.0)),
            frequency_penalty=float(body.get("frequency_penalty", 0.0)),
            n=int(body.get("n", 1)),
            best_of=(int(body["best_of"]) if body.get("best_of")
                     else None),
            logprobs=(int(lp) if lp is not None and lp is not False
                      else None),
            seed=(int(body["seed"]) if body.get("seed") is not None
                  else None),
            max_time_ms=(float(body["max_time_ms"])
                         if body.get("max_time_ms") is not None
                         else None),
            ignore_eos=bool(body.get("ignore_eos", False)),
            qos=(str(body["qos"]) if body.get("qos") else None),
        )

    @staticmethod
    def _tenant_of(headers) -> str:
        """Tenant identity for fair queuing / rate limits: explicit
        X-Tenant-Id header, else a stable hash of the API key
        (Authorization header), else the shared 'default' bucket."""
        tid = headers.get("X-Tenant-Id")
        if tid:
            return str(tid)[:64]
        auth = headers.get("Authorization")
        if auth:
            return "key-" + hashlib.sha256(
                auth.encode("utf-8", "replace")).hexdigest()[:12]
        return "default"

    def _run_request(self, token_ids, params, stream_cb=None,
                     stop_strs=(), disconnect_check=None,
                     cancel_cb=None, rid=None, trace=None,
                     seed_ids=None):
        """Returns (rid, {index: ids}, {index: logprob entries},
        {index: finish_reason}, {index: final text}, {index: error}).

        stream_cb(text_delta, index) when set — deltas come from the
        ACCUMULATED decode (robust to multi-token characters), with a
        holdback of len(longest stop)-1 chars so a stop string never
        leaks into the stream. `stop_strs` are the OpenAI `stop`
        sequences (reference vllm SamplingParams.stop): output truncates
        at the first match; a single-choice request aborts early.

        `disconnect_check()` is polled while waiting (both paths); when
        it reports the client gone — or a streaming SSE write fails —
        the request is aborted: the engine frees the slot AND drops the
        prompt's prefix-cache entry, so a hung-up client stops costing
        HBM immediately. `cancel_cb()` fires exactly once on such a
        client-driven cancellation (the counter hook). When `rid` is
        given the request was already added to the engine (the HTTP
        layer admits BEFORE committing stream headers, so an admission
        shed can still be a clean 429/503); otherwise add here."""
        if rid is None:
            rid = f"cmpl-{uuid.uuid4().hex[:16]}"
            self.engine.add_request(rid, token_ids, params, trace=trace)
            self.loop.notify()
        out_ids: dict = {}
        out_lps: dict = {}
        reasons: dict = {}
        errors: dict = {}     # index -> structured engine error
        texts: dict = {}      # index -> full decoded (possibly cut) text
        emitted: dict = {}    # index -> chars already streamed
        scanned: dict = {}    # index -> chars already stop-scanned
        detoks: dict = {}     # index -> _IncrementalDetok
        stopped: set = set()
        hold = max((len(s) for s in stop_strs), default=0)
        n_choices = max(params.n, 1)
        # streaming and stop-scanning share one incremental detokenizer
        # per choice (O(n) total, append-only deltas); plain stop-free
        # requests decode once at the end
        live_decode = bool(stop_strs) or stream_cb is not None
        cancelled = [False]          # cancel_cb fired (at most once)
        # index -> push time of the oldest output whose text no chunk
        # has carried yet / of the newest output (a held-back tail)
        oldest_push: dict = {}
        newest_push: dict = {}
        # index -> the pass counts (`RequestOutput.steps`, a family whose
        # step is a block) of the tokens no chunk has carried yet
        owed_steps: dict = {}
        if seed_ids:
            # a resumed (migrated-in) request: the engine only emits
            # tokens generated since the claim, but the client is owed
            # the WHOLE completion and decode(a + b) is not
            # decode(a) + decode(b) for real tokenizers — seed the
            # accumulated state with the pre-migration ids and mark
            # their text already emitted and already stop-scanned (the
            # source replica streamed it before handing off), so the
            # continuation's first delta carries the boundary
            # separator and the buffered response detokenizes pre +
            # post together
            out_ids[0] = list(seed_ids)
            pre_text = self._decode_text(list(seed_ids))
            emitted[0] = len(pre_text)
            scanned[0] = len(pre_text)
            if live_decode:
                det = detoks[0] = _IncrementalDetok(self._decode_text)
                det.push(list(seed_ids))
                texts[0] = det.text

        def cancel_once():
            if not cancelled[0]:
                cancelled[0] = True
                if cancel_cb is not None:
                    try:
                        cancel_cb()
                    except Exception:
                        pass         # accounting must not alter the abort

        def emit(idx, upto):
            nonlocal stream_cb
            if stream_cb is None:
                return
            full = texts[idx]
            start = emitted.get(idx, 0)
            upto = min(upto, len(full))
            if upto > start:
                try:
                    if idx in owed_steps:
                        # several tokens an event, one integer a token
                        stream_cb(full[start:upto], idx,
                                  steps=owed_steps.pop(idx))
                    else:
                        stream_cb(full[start:upto], idx)
                    emitted[idx] = upto
                    t_push = (oldest_push.pop(idx, None)
                              or newest_push.get(idx))
                    if t_push:
                        self._m_stream_delivery.observe(
                            time.perf_counter() - t_push)
                except OSError:
                    # client went away mid-stream: free the slot (the
                    # abort also drops the prompt's prefix-cache
                    # entry), then keep draining until the engine
                    # emits the abort-finish (reference
                    # api_server.py:371 disconnect -> abort)
                    cancel_once()
                    self.engine.abort_request(rid)
                    self.loop.notify()
                    stream_cb = None

        def scan_stop(idx):
            """Scan the unseen tail of the stable text for the earliest
            stop string; returns the cut position or -1."""
            full = texts[idx]
            scan0 = max(0, scanned.get(idx, 0) - max(hold - 1, 0))
            cut = -1
            for s in stop_strs:
                p = full.find(s, scan0)
                if p != -1 and (cut == -1 or p < cut):
                    cut = p
            scanned[idx] = len(full)
            return cut

        def apply_stop(idx, cut, batch_len):
            texts[idx] = texts[idx][:cut]
            stopped.add(idx)
            reasons[idx] = "stop"
            emit(idx, cut)
            # drop the tokens whose text fell past the cut (usage must
            # bill the VISIBLE completion): walk back this batch's
            # tokens while the stop still matches without them
            ids = out_ids[idx]
            keep = len(ids)
            lo = keep - batch_len
            while keep > lo:
                shorter = self._decode_text(ids[:keep - 1])
                if any(s in shorter for s in stop_strs):
                    keep -= 1
                else:
                    break
            del ids[keep:]
            if idx in out_lps:
                del out_lps[idx][keep:]
            if stopped >= set(range(n_choices)):
                # every choice done: stop generating
                self.engine.abort_request(rid)
                self.loop.notify()

        done = False
        aborted = False
        next_conn_check = time.time() + 0.25
        while not done:
            if disconnect_check is not None and not aborted \
                    and time.time() >= next_conn_check:
                next_conn_check = time.time() + 0.25
                try:
                    gone = disconnect_check()
                except Exception:
                    gone = True
                if gone:
                    # client hung up mid-generation: cancel, then keep
                    # draining until the engine emits the abort-finish
                    aborted = True
                    cancel_once()
                    self.engine.abort_request(rid)
                    self.loop.notify()
            outs = self.engine.get_outputs(rid)
            if not outs:
                time.sleep(0.002)
                continue
            for o in outs:
                idx = o.index
                if idx not in stopped:
                    # stopped choices freeze: ids/logprobs past the stop
                    # would inflate usage and desync from the cut text
                    out_ids.setdefault(idx, []).extend(o.new_token_ids)
                    if o.logprobs:
                        out_lps.setdefault(idx, []).extend(o.logprobs)
                    if o.steps is not None and o.new_token_ids:
                        owed_steps.setdefault(idx, []).extend(o.steps)
                if live_decode and o.new_token_ids and idx not in stopped:
                    oldest_push.setdefault(idx, o.t_push)
                    newest_push[idx] = o.t_push
                    det = detoks.get(idx)
                    if det is None:
                        det = detoks[idx] = _IncrementalDetok(
                            self._decode_text)
                    det.push(o.new_token_ids)
                    texts[idx] = det.text
                    cut = scan_stop(idx) if stop_strs else -1
                    if cut != -1:
                        apply_stop(idx, cut, len(o.new_token_ids))
                    else:
                        emit(idx, len(det.text) - hold + 1
                             if hold else len(det.text))
                if o.finish_reason is not None:
                    reasons.setdefault(idx, o.finish_reason)
                if o.error is not None:
                    errors.setdefault(idx, o.error)
                if o.finished:
                    reasons.setdefault(idx, o.finish_reason or "stop")
                    done = True
        for idx, det in detoks.items():
            if idx in stopped:
                continue
            det.flush()                      # drain the held-back tail
            texts[idx] = det.text
            cut = scan_stop(idx) if stop_strs else -1
            if cut != -1:
                apply_stop(idx, cut, len(det.ids))
        for idx in list(texts):
            emit(idx, len(texts[idx]))       # flush the holdback
        for i in range(n_choices):
            out_ids.setdefault(i, [])
            texts.setdefault(i, self._decode_text(out_ids[i]))
            reasons.setdefault(i, reasons.get(0, "stop"))
        # the synthetic fan-out closer carries no tokens under its own
        # index; drop any empty phantom choice beyond n
        out_ids = {i: v for i, v in out_ids.items() if i < n_choices}
        texts = {i: v for i, v in texts.items() if i < n_choices}
        return rid, out_ids, out_lps, reasons, texts, errors

    # -- KV handoff (prefill side) ------------------------------------------

    def _count_handoff(self, key: str) -> None:
        with self._handoff_lock:
            self._handoff_counts[key] += 1
        self._m_handoff[key].inc()

    def _next_handoff_attempt(self) -> int:
        with self._handoff_lock:
            self._handoff_attempts += 1
            return self._handoff_attempts

    def _count_reject(self, reason: str) -> None:
        with self._handoff_lock:
            self._reject_counts[reason] = \
                self._reject_counts.get(reason, 0) + 1
        self._m_rejects.labels(reason).inc()

    def handoff_snapshot(self) -> dict:
        """The /v1/stats "handoff" block: flat counters the router's
        stats poll turns into per-replica deltas."""
        with self._handoff_lock:
            return dict(self._handoff_counts)

    def rejects_snapshot(self) -> dict:
        """The /v1/stats "wire_rejects" block: framed-payload
        rejections at receive, by reason."""
        with self._handoff_lock:
            return dict(self._reject_counts)

    def _handoff_eligible(self, body: dict, params) -> List[str]:
        """Decode targets for this request, empty when the request must
        decode locally: only a prefill-role replica hands off, only
        non-streaming single-choice requests (the decode replica owns
        the whole token stream), and only when the router named targets
        (X-Handoff-Targets is absent on direct client connections)."""
        if self.role != "prefill" or body.get("stream"):
            return []
        if max(params.n, 1) != 1 or params.best_of is not None:
            return []
        hdr = body.get("_handoff_targets")
        if not hdr:
            return []
        return [t.strip() for t in str(hdr).split(",") if t.strip()]

    def _prefill_and_handoff(self, ids, params, body: dict,
                             targets: List[str],
                             trace=None) -> Optional[dict]:
        """Run chunked prefill locally (a 1-token generation, which
        leaves the prompt's quantized KV snapshot in the prefix cache),
        then ship the snapshot + request to a decode replica and relay
        its completion JSON. Returns None when every attempt failed —
        the caller falls back to local mixed decode, reusing the same
        snapshot as its own prefix seed, so the request is NEVER lost
        to a dead decode target (and the prefill work is not wasted).

        Each attempt gets resolve_handoff_timeout_ms() of wall time;
        failures retry with bounded exponential backoff, rotating
        through `targets`, up to resolve_handoff_retries() retries.
        The handoff_drop chaos fault (robustness/faults.py) is
        consulted per attempt and makes it fail as if the wire dropped
        the transfer."""
        probe = dataclasses.replace(params, max_tokens=1, n=1,
                                    best_of=None, logprobs=None)
        _, _, _, reasons, _, _ = self._run_request(ids, probe,
                                                   trace=trace)
        if any(r in ("error",) + _TIMEOUT_REASONS
               for r in reasons.values()):
            return None          # prefill itself failed: local path decides
        entry = self.engine.export_prefix_snapshot(ids)
        if entry is None:
            return None          # snapshot evicted/disabled: decode locally
        req = {k: v for k, v in body.items()
               if k not in ("stream", "prompt", "messages",
                            "_handoff_targets", "_traceparent")}
        # the transfer claims its own (local) span, but the decode
        # target parents its spans under the span id WE were handed —
        # the router's, the nearest crash-durable ancestor — so a
        # prefill death mid-relay cannot orphan the decode leg of the
        # timeline (body, not header alone — the relay's _completions
        # re-reads it from the staged request)
        handoff_span = new_span_id() if trace is not None else None
        t_handoff0 = time.time()
        hdrs = {"Content-Type": "application/octet-stream",
                "X-Tenant-Id": params.tenant or "default"}
        if trace is not None:
            req["_traceparent"] = make_traceparent(trace[0], trace[1])
            hdrs["traceparent"] = req["_traceparent"]
        # checksummed frame (serving/wire.py): a bit-flipped base64
        # body now dies at the receiver's CRC check as a structured
        # 400 instead of deserializing into garbage KV
        payload = frame_payload({
            "prompt": [int(t) for t in ids],
            "planes": planes_to_wire(entry),
            "request": req,
        })
        import urllib.request

        attempts = self._handoff_retries + 1
        delay = 0.05
        for i in range(attempts):
            target = targets[i % len(targets)]
            step = self._next_handoff_attempt()
            if self.engine.faults.drop_point("handoff", step):
                self._count_handoff("dropped")
            else:
                data = payload
                if self.engine.faults.corrupt_point("handoff", step):
                    data = corrupt_frame(payload)
                try:
                    d = self.engine.faults.net_delay_ms("handoff", step)
                    if d:
                        time.sleep(d / 1000.0)
                    if self.engine.faults.net_dropped("handoff", step):
                        raise OSError(
                            "injected connection reset (net_drop)")
                    r = urllib.request.Request(
                        f"http://{target}/v1/internal/kv_handoff",
                        data=data, method="POST", headers=hdrs)
                    with urllib.request.urlopen(
                            r, timeout=self._handoff_timeout_ms
                            / 1000.0) as resp:
                        if resp.status == 200:
                            out = json.loads(resp.read())
                            self._count_handoff("sends")
                            if trace is not None:
                                if not resp.headers.get("X-Trace-Span"):
                                    # decode target answered but never
                                    # reported its child span: the
                                    # timeline's decode leg is missing
                                    self._m_span_orphans.inc()
                                self.engine.spans.record(
                                    "kv_handoff", trace[0],
                                    span_id=handoff_span,
                                    parent_id=trace[1],
                                    t_start=t_handoff0,
                                    t_end=time.time(),
                                    target=target, attempt=i + 1)
                            return out
                except Exception:
                    pass         # timeout, refused, 5xx, dead target
            if i + 1 < attempts:
                self._count_handoff("retries")
                if trace is not None:
                    self.engine.spans.annotate(
                        trace[0], "handoff_retry",
                        parent_id=handoff_span, attempt=i + 1,
                        target=target)
                time.sleep(delay)
                delay = min(delay * 2, 1.0)
        self._count_handoff("fallbacks")
        self.engine.flight.record(
            "handoff_fallback", targets=list(targets),
            attempts=attempts, prompt_len=len(ids),
            **({"trace_id": trace[0]} if trace is not None else {}))
        if trace is not None:
            # the abandoned transfer still claims its span (failed=True)
            # so retry/fallback annotations parented under it resolve
            self.engine.spans.record(
                "kv_handoff", trace[0], span_id=handoff_span,
                parent_id=trace[1], t_start=t_handoff0,
                t_end=time.time(), failed=True, attempts=attempts)
            self.engine.spans.annotate(
                trace[0], "handoff_fallback", parent_id=handoff_span,
                targets=list(targets), attempts=attempts)
        return None

    # -- live migration (source side) ---------------------------------------

    def _take_migrated_info(self, rid: str) -> dict:
        with self._handoff_lock:
            return self._migrated_info.pop(rid, {})

    def migrate_out(self, targets: List[str], rids=None,
                    max_sequences=None, qos=None) -> dict:
        """Migrate in-flight mid-decode sequences to healthy peers and
        report per-sequence outcomes. The planned-disruption entry
        point: the router calls it (POST /v1/admin/migrate_out) before
        a rolling-restart SIGTERM or an autoscale retirement,
        begin_drain calls it when handed migrate targets, and the
        brownout ladder's level-3 option calls it with qos="batch".
        With live migration off (or no targets) every sequence is
        skipped and callers fall back to drain-and-replay — the
        pre-migration behavior, zero-5xx but not zero-loss."""
        results: List[dict] = []
        summary = {"migrated": 0, "failed": 0, "skipped": 0,
                   "results": results}
        targets = [str(t).strip() for t in (targets or [])
                   if str(t).strip()]
        if self.live_migration == "off" or not targets:
            return summary
        todo = (list(rids) if rids
                else self.engine.active_request_ids(qos=qos))
        if max_sequences is not None:
            todo = todo[:int(max_sequences)]
        for rid in todo:
            res = self._migrate_one(rid, targets)
            results.append(res)
            o = res["outcome"]
            if o == "migrated":
                summary["migrated"] += 1
            elif o == "unexportable":
                summary["skipped"] += 1
            else:
                summary["failed"] += 1
        return summary

    def _migrate_one(self, rid: str, targets: List[str]) -> dict:
        """Export one mid-decode sequence and ship it to the first
        target that acks. Commit (engine.finish_migrated) happens ONLY
        on a 200 carrying the resume_id; every other ending resumes
        the sequence locally from its own exported planes
        (engine.resume_local) — the request is never lost, at worst it
        keeps decoding where it already was. The migration_drop /
        migration_corrupt and net_latency / net_drop chaos kinds
        (robustness/faults.py) hook every attempt."""
        state = self.engine.export_sequence(
            rid, timeout_sec=self._migrate_timeout_ms / 1000.0)
        if state is None:
            # finished, already migrating, or not mid-decode here —
            # nothing was suspended, nothing to undo
            return {"request_id": rid, "outcome": "unexportable"}
        planes = state.pop("planes")
        doc = dict(state, planes=planes_to_wire(planes))
        tr = state.get("trace")
        payload = frame_payload(doc)
        if len(payload) > self._migrate_max_bytes:
            self.engine.resume_local(rid)
            self.loop.notify()
            self.engine.flight.record(
                "migration_too_large", request_id=rid,
                bytes=len(payload), cap=self._migrate_max_bytes)
            return {"request_id": rid, "outcome": "too_large",
                    "bytes": len(payload)}
        import urllib.request

        t0 = time.time()
        span_id = new_span_id() if tr else None
        attempts = self._handoff_retries + 1
        delay = 0.05
        for i in range(attempts):
            target = targets[i % len(targets)]
            step = self._next_handoff_attempt()
            if self.engine.faults.drop_point("migrate_send", step):
                pass             # injected wire loss: no bytes moved
            else:
                data = payload
                if self.engine.faults.corrupt_point("migrate", step):
                    data = corrupt_frame(payload)
                try:
                    d = self.engine.faults.net_delay_ms("migrate", step)
                    if d:
                        time.sleep(d / 1000.0)
                    if self.engine.faults.net_dropped("migrate", step):
                        raise OSError(
                            "injected connection reset (net_drop)")
                    r = urllib.request.Request(
                        f"http://{target}/v1/internal/migrate_in",
                        data=data, method="POST",
                        headers={"Content-Type":
                                 "application/octet-stream"})
                    with urllib.request.urlopen(
                            r, timeout=self._migrate_timeout_ms
                            / 1000.0) as resp:
                        if resp.status == 200:
                            ack = json.loads(resp.read())
                            resume_id = str(ack.get("resume_id")
                                            or state["resume_id"])
                            with self._handoff_lock:
                                self._migrated_info[rid] = {
                                    "resume_id": resume_id,
                                    "target": target}
                            self.engine.finish_migrated(
                                rid, target, resume_id)
                            self.loop.notify()
                            if tr:
                                self.engine.spans.record(
                                    "migrate.out", tr[0],
                                    span_id=span_id, parent_id=tr[1],
                                    t_start=t0, t_end=time.time(),
                                    target=target, attempt=i + 1,
                                    bytes=len(payload))
                            return {"request_id": rid,
                                    "outcome": "migrated",
                                    "target": target,
                                    "resume_id": resume_id,
                                    "attempts": i + 1}
                except Exception:
                    pass         # timeout, refused, 4xx/5xx, dead target
            if i + 1 < attempts:
                time.sleep(delay)
                delay = min(delay * 2, 1.0)
        # every attempt failed: the sequence resumes HERE from its own
        # exported planes — zero tokens lost, zero recompute when the
        # local reseed lands
        self.engine.resume_local(rid)
        self.loop.notify()
        if tr:
            self.engine.spans.record(
                "migrate.out", tr[0], span_id=span_id,
                parent_id=tr[1], t_start=t0, t_end=time.time(),
                failed=True, attempts=attempts)
        return {"request_id": rid, "outcome": "failed",
                "attempts": attempts}

    # -- http ---------------------------------------------------------------

    def make_handler(server):
        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):   # quiet
                pass

            def parse_request(self):
                # called with the request line just read: the ingest
                # phase of a completion counts from here
                self._t_request = time.perf_counter()
                return super().parse_request()

            def _json(self, code: int, obj: dict, headers=()):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                # _trace_headers: response headers set by an outer
                # handler layer (_kv_handoff's X-Trace-Span ack rides
                # on the relayed _completions response)
                for k, v in (tuple(headers)
                             + tuple(getattr(self, "_trace_headers",
                                             ()))):
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _draining_503(self):
                # shedding during drain: tell the client when a fresh
                # replica should be up (reference: k8s preStop drain)
                retry = server.engine.drain_retry_after_sec()
                return self._json(
                    503, {"error": {
                        "message": "server is draining; retry against "
                                   "another replica",
                        "type": "unavailable", "code": 503,
                        "retry_after": retry}},
                    headers=(("Retry-After", str(retry)),))

            def _shed_response(self, e: RequestShed):
                # early load shedding: the overload controller refused
                # admission (bounded queue, rate limit, doomed-work
                # test, or brownout) — 429 for per-tenant limits, 503
                # for server-wide pressure, both with a Retry-After
                # computed from the measured drain rate and ledger
                # headroom so clients back off for the right duration
                retry = int(e.retry_after_sec)
                return self._json(
                    e.http_status, {"error": {
                        "message": f"request shed ({e.reason}): "
                                   f"{e.detail or 'server overloaded'}",
                        "type": ("rate_limited" if e.http_status == 429
                                 else "overloaded"),
                        "code": e.http_status, "reason": e.reason,
                        "qos": e.qos, "tenant": e.tenant,
                        "retry_after": retry}},
                    headers=(("Retry-After", str(retry)),))

            def do_GET(self):
                if self.path == "/v1/models":
                    self._json(200, {"object": "list", "data": [
                        {"id": server.model_name, "object": "model"}]})
                elif self.path in ("/health", "/ping"):
                    # a draining replica reports 503 so load balancers
                    # stop routing to it while in-flight work finishes;
                    # a WEDGED one (work pending, step loop frozen)
                    # reports 503 so a supervisor replaces it — the
                    # process answering HTTP proves nothing about the
                    # engine thread. A stale heartbeat during a jit
                    # compile is the compiler working (first call per
                    # shape bucket legitimately blocks step() for
                    # seconds-to-minutes), not a hang — report busy,
                    # not wedged, or every cold replica gets killed
                    # mid-compile by its supervisor.
                    age = server.engine.step_heartbeat_age()
                    # every body says how old the process is and when
                    # it emitted its first token (None before it): an
                    # operator's cold start, read where readiness is
                    started = startup_snapshot(programs=False)
                    ages = {"age_s": started["process_age_s"],
                            "first_token_s":
                            started["marks"].get("first_token")}
                    if server.engine.draining:
                        self._json(503, {"status": "draining", **ages})
                    elif server.engine.has_unfinished() \
                            and age > server.wedge_sec:
                        ages["heartbeat_age_sec"] = round(age, 3)
                        if compiles_in_progress():
                            self._json(200, {"status": "compiling",
                                             **ages})
                        else:
                            self._json(503, {"status": "wedged", **ages})
                    else:
                        self._json(200, {"status": "ok", **ages})
                elif self.path == "/metrics":
                    body = server.engine.registry.render().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/v1/stats":
                    snap = server.engine.stats_snapshot()
                    snap["role"] = server.role
                    snap["handoff"] = server.handoff_snapshot()
                    snap["wire_rejects"] = server.rejects_snapshot()
                    snap["live_migration"] = server.live_migration
                    self._json(200, snap)
                elif self.path == "/v1/memory":
                    # ledger static report + live device stats +
                    # headroom math (observability/memory.py)
                    self._json(200, _jsonable(
                        server.engine.memory_snapshot()))
                elif self.path == "/v1/debug/dump":
                    # same document the engine writes to
                    # $BIGDL_TPU_POSTMORTEM_DIR, served live
                    self._json(200, _jsonable(
                        server.engine.postmortem("on_demand")))
                elif self.path == "/v1/perf":
                    # live roofline attribution + sentinel state
                    # (engine.perf_snapshot); the router's
                    # /v1/admin/profiler and /v1/router/stats aggregate
                    # this per replica
                    self._json(200, _jsonable(
                        server.engine.perf_snapshot()))
                elif self.path == "/v1/quality":
                    # quantization-error attribution + live decode
                    # quality + golden-probe NLL + QualitySentinel
                    # state (engine.quality_snapshot); the router's
                    # /v1/router/stats aggregates the compact subset
                    self._json(200, _jsonable(
                        server.engine.quality_snapshot()))
                elif self.path == "/v1/slo":
                    # per-replica SLO state: resolved spec, current
                    # burn rates per (qos, objective, window), active
                    # alerts (observability/slo.py); the router
                    # aggregates this fleet-wide in /v1/router/stats
                    self._json(200, _jsonable(
                        server.engine.slo.snapshot()))
                elif self.path == "/v1/usage":
                    # per-tenant usage rollup (observability/usage.py):
                    # totals + current token burn, reconciling exactly
                    # with bigdl_tpu_tenant_requests_total
                    self._json(200, _jsonable(
                        server.engine.usage.snapshot()))
                elif self.path == "/v1/profiler/status":
                    from bigdl_tpu.utils import profiling

                    self._json(200, profiling.profiler_status())
                elif self.path.startswith("/v1/internal/spans"):
                    # the router's /v1/trace/{id} fan-out target:
                    # completed spans for one trace, stamped with this
                    # replica's wall clock so the router can estimate
                    # and subtract clock skew
                    from urllib.parse import parse_qs, urlparse

                    q = parse_qs(urlparse(self.path).query)
                    tid = (q.get("trace_id") or [None])[0]
                    doc = {"now": time.time(),
                           "service": server.engine.spans.service}
                    if tid:
                        doc["spans"] = \
                            server.engine.spans.spans_for(tid)
                    else:
                        doc["traces"] = \
                            server.engine.spans.recent_traces()
                    self._json(200, doc)
                else:
                    self._json(404, {"error": "not found"})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                internal = self.path.startswith("/v1/internal/")
                if self.path == "/v1/internal/migrate_in" \
                        and n > server._migrate_max_bytes:
                    # refuse BEFORE reading the body: an oversized
                    # export must not stall the intake thread
                    server._count_reject("too_large")
                    return self._json(413, {"error": {
                        "message": f"migration payload {n} bytes "
                                   f"exceeds BIGDL_TPU_MIGRATE_MAX_"
                                   f"BYTES={server._migrate_max_bytes}",
                        "type": "bad_wire_frame",
                        "reason": "too_large", "code": 413}})
                raw = self.rfile.read(n) if n else b"{}"
                if internal and is_framed(raw):
                    # checksummed frame (serving/wire.py): a corrupt or
                    # version-skewed payload dies here as a structured
                    # 400 the sender's retry ladder understands
                    try:
                        body = unframe_payload(raw)
                    except WireError as e:
                        server._count_reject(e.reason)
                        return self._json(400, {"error": {
                            "message": str(e),
                            "type": "bad_wire_frame",
                            "reason": e.reason, "code": 400}})
                    if not isinstance(body, dict):
                        server._count_reject("json")
                        return self._json(400, {"error": {
                            "message": "frame body must be a JSON "
                                       "object",
                            "type": "bad_wire_frame",
                            "reason": "json", "code": 400}})
                else:
                    # legacy bare-JSON internal payloads stay accepted
                    # for one version of mixed-fleet compatibility
                    try:
                        body = json.loads(raw or b"{}")
                    except json.JSONDecodeError:
                        return self._json(400, {"error": "bad json"})
                try:
                    if self.path == "/v1/completions":
                        return self._completions(body, chat=False)
                    if self.path == "/v1/chat/completions":
                        return self._completions(body, chat=True)
                    if self.path == "/v1/embeddings":
                        return self._embeddings(body)
                    if self.path == "/v1/internal/kv_handoff":
                        return self._kv_handoff(body)
                    if self.path == "/v1/internal/migrate_in":
                        return self._migrate_in(body)
                    if self.path == "/v1/admin/migrate_out":
                        return self._admin_migrate_out(body)
                    if self.path == "/v1/profiler/start":
                        return self._profiler(body, start=True)
                    if self.path == "/v1/profiler/stop":
                        return self._profiler(body, start=False)
                except EngineDraining:
                    return self._draining_503()
                except RequestShed as e:
                    return self._shed_response(e)
                except ValueError as e:
                    return self._json(400, {"error": str(e)})
                self._json(404, {"error": "not found"})

            def _profiler(self, body: dict, start: bool):
                from bigdl_tpu.utils import profiling

                try:
                    if start:
                        log_dir = body.get("log_dir")
                        if not log_dir:
                            return self._json(
                                400, {"error": "'log_dir' required"})
                        out = profiling.start_profiler(
                            log_dir,
                            max_sec=body.get("duration_sec"),
                            capture_id=body.get("capture_id"))
                    else:
                        out = profiling.stop_profiler()
                except RuntimeError as e:
                    # double-start / stop-without-start / dir over cap
                    return self._json(409, {"error": str(e)})
                self._json(200, out)

            def _kv_handoff(self, body: dict):
                """Decode side of the disaggregated prefill/decode
                split: accept a prefill replica's KV snapshot, stage it
                into the prefix cache (engine.stage_handoff — the
                engine loop drains it before the next admission), then
                run the request through the NORMAL completion path. The
                admission's prefix seeding picks the staged planes up,
                so decode skips the already-prefilled tokens while the
                output stays byte-identical to a from-scratch run.
                Shedding/draining surface as the usual 429/503 — the
                prefill side treats any non-200 as a failed attempt."""
                prompt = body.get("prompt")
                if not (isinstance(prompt, list) and prompt
                        and all(isinstance(t, int) for t in prompt)):
                    return self._json(
                        400, {"error": "'prompt' must be a non-empty "
                                       "token-id list"})
                planes = planes_from_wire(body.get("planes"))
                req = body.get("request")
                req = dict(req) if isinstance(req, dict) else {}
                req.pop("stream", None)
                req["prompt"] = prompt
                # trace propagation: claim a child span for the decode
                # leg, re-parent the staged request under it, and echo
                # its id (X-Trace-Span) so the prefill side knows the
                # decode leg reported — a missing ack counts toward
                # bigdl_tpu_handoff_span_orphans_total over there
                tp = (req.get("_traceparent")
                      or self.headers.get("traceparent"))
                trace = parse_traceparent(tp)
                t_accept0 = time.time()
                sid = None
                if trace is not None:
                    sid = new_span_id()
                    req["_traceparent"] = make_traceparent(trace[0],
                                                           sid)
                    self._trace_headers = (("X-Trace-Span", sid),)
                server.engine.stage_handoff(prompt, planes)
                server._count_handoff("accepted")
                try:
                    return self._completions(req, chat=False)
                finally:
                    if trace is not None:
                        server.engine.spans.record(
                            "kv_handoff.decode", trace[0],
                            span_id=sid, parent_id=trace[1],
                            t_start=t_accept0, t_end=time.time(),
                            prompt_len=len(prompt))

            def _migrate_in(self, body: dict):
                """Target side of live migration: accept one
                mid-decode sequence's exported state (framed and
                CRC-checked in do_POST), stage it for the resumed
                request to claim (engine.stage_migration — the engine
                loop imports the KV pages before the next admission),
                and ack with the resume_id the client must present
                (X-Resume-Id). The source treats any non-200 as a
                failed attempt and falls back (retry / local resume) —
                including the injected recv/commit drops below, which
                emulate a request lost before intake and a commit ack
                lost on the wire (state staged, source never told; the
                staging TTL reclaims it unclaimed, so no tokens ever
                reach a client twice)."""
                if server.live_migration == "off":
                    return self._json(503, {"error": {
                        "message": "live migration disabled "
                                   "(BIGDL_TPU_LIVE_MIGRATION=off)",
                        "type": "unavailable", "code": 503}})
                if server.engine.draining:
                    return self._draining_503()
                step = server._next_handoff_attempt()
                if server.engine.faults.drop_point("migrate_recv",
                                                   step):
                    return self._json(503, {"error": {
                        "message": "injected migrate_recv drop",
                        "type": "unavailable", "code": 503}})
                t0 = time.time()
                planes = planes_from_wire(body.get("planes"))
                state = dict(body)
                state["planes"] = planes
                resume_id = server.engine.stage_migration(state)
                server.loop.notify()
                tr = state.get("trace")
                if tr:
                    server.engine.spans.record(
                        "migrate.in", tr[0], span_id=new_span_id(),
                        parent_id=tr[1], t_start=t0, t_end=time.time(),
                        resume_id=resume_id,
                        kv_len=state.get("kv_len"))
                if server.engine.faults.drop_point("migrate_commit",
                                                   step):
                    # the state IS staged — only the ack dies. The
                    # source resumes locally; the staged copy expires
                    # unclaimed (engine._migration_ttl)
                    return self._json(503, {"error": {
                        "message": "injected migrate_commit drop",
                        "type": "unavailable", "code": 503}})
                return self._json(200, {"resume_id": resume_id,
                                        "staged": True})

            def _admin_migrate_out(self, body: dict):
                """Operator/router entry point for planned disruption:
                migrate in-flight sequences to the named healthy peers
                and report per-sequence outcomes. The router calls
                this before the SIGTERM of a rolling restart or an
                autoscale retirement, so the drain that follows has
                nothing left to recompute."""
                targets = body.get("targets") or []
                if isinstance(targets, str):
                    targets = targets.split(",")
                targets = [str(t).strip() for t in targets
                           if str(t).strip()]
                if not targets:
                    return self._json(
                        400, {"error": "'targets' must name at least "
                                       "one host:port peer"})
                out = server.migrate_out(
                    targets, rids=body.get("request_ids"),
                    max_sequences=body.get("max_sequences"),
                    qos=body.get("qos"))
                self._json(200, out)

            def _embeddings(self, body: dict):
                if server.embedder is None or \
                        server.embedder_tokenizer is None:
                    return self._json(
                        400, {"error": "no embedding model configured "
                              "(construct OpenAIServer with embedder= "
                              "and embedder_tokenizer=)"})
                inputs = body.get("input")
                if isinstance(inputs, str):
                    inputs = [inputs]
                if not isinstance(inputs, list) or not inputs or \
                        not all(isinstance(t, str) for t in inputs):
                    return self._json(
                        400, {"error": "'input' must be a string or a "
                              "non-empty list of strings"})
                vecs, n_tok = server.embedder.embed_texts(
                    inputs, server.embedder_tokenizer,
                    with_counts=True)
                self._json(200, {
                    "object": "list",
                    "model": body.get("model", server.model_name),
                    "data": [
                        {"object": "embedding", "index": i,
                         "embedding": [float(x) for x in v]}
                        for i, v in enumerate(vecs)],
                    "usage": {"prompt_tokens": int(n_tok),
                              "total_tokens": int(n_tok)},
                })

            def _completions(self, body: dict, chat: bool):
                if chat:
                    prompt = _chat_to_prompt(body.get("messages", []),
                                             server.tokenizer)
                else:
                    prompt = body.get("prompt", "")
                ids = server._encode(prompt)
                params = server._params(body)
                params = dataclasses.replace(
                    params, tenant=server._tenant_of(self.headers))
                stops = body.get("stop") or ()
                if isinstance(stops, str):
                    stops = (stops,)
                stops = tuple(s for s in stops if s)
                if stops and getattr(server.engine.family, "block_spec",
                                     None) is not None:
                    # (ValueError: a 400, as the engine's own refusals)
                    raise ValueError(
                        "stop strings hold text back from an event, and a "
                        "block family's event says `steps`, one integer a "
                        "token it delivers: the two would part. Cut the "
                        "text at the client, or end at `max_tokens` / EOS")
                created = int(time.time())
                # trace context: router/client header, or the staged
                # _traceparent a kv_handoff relay carries in its body
                tp = (self.headers.get("traceparent")
                      or body.get("_traceparent"))
                trace = parse_traceparent(tp)
                # shed BEFORE the stream branch commits its 200 header
                # (add_request would raise EngineDraining anyway, but by
                # then a streaming response is already half-written)
                if server.engine.draining:
                    return self._draining_503()
                # disaggregated path: a prefill-role replica handed a
                # non-streaming request by the router (X-Handoff-Targets
                # names the decode candidates) prefills locally, ships
                # the KV snapshot, and relays the decode replica's
                # response verbatim. A None return means every transfer
                # attempt failed — fall through to the normal local
                # path below, which reuses the snapshot as its own
                # prefix seed (the handoff ladder's terminal fallback:
                # the request is never lost to a dead decode target).
                # a migrated sequence arriving at its new home: the
                # router re-forwards the original request with
                # X-Resume-Id, and claiming the staged state resumes
                # generation mid-decode (zero recompute). A claim miss
                # — staging TTL expired, wrong replica — falls through
                # to a fresh replay: slower, never wrong.
                resume_state = None
                rh = self.headers.get("X-Resume-Id")
                if rh:
                    resume_state = server.engine.claim_migration(rh)
                pre_ids: List[int] = []
                if resume_state is not None:
                    # tokens generated before this replica took over
                    # (any earlier hop's output rode into the exported
                    # prompt; generated_offset marks where the true
                    # prompt ends) — seeded into _run_request so the
                    # response covers the full completion
                    off = int(resume_state.get("generated_offset")
                              or 0)
                    pids = list(
                        resume_state.get("prompt_token_ids") or [])
                    pre_ids = (pids[len(pids) - off:] if off else []) \
                        + list(resume_state.get("generated") or [])
                hdr = self.headers.get("X-Handoff-Targets")
                if hdr and "_handoff_targets" not in body:
                    body = dict(body)
                    body["_handoff_targets"] = hdr
                # (chat keeps local decode: the relayed JSON is in
                # text_completion shape)
                targets = (() if chat or resume_state is not None
                           else server._handoff_eligible(body, params))
                if targets:
                    out = server._prefill_and_handoff(
                        ids, params, body, targets, trace=trace)
                    if out is not None:
                        return self._json(200, out)
                # admit BEFORE the stream branch for the same reason:
                # overload control (RequestShed -> 429/503 +
                # Retry-After, handled in do_POST) must reject doomed
                # work as a clean status line, not a broken SSE body
                rid = f"cmpl-{uuid.uuid4().hex[:16]}"
                if resume_state is not None:
                    server.engine.resume_migrated_request(
                        rid, resume_state, trace=trace)
                else:
                    server.engine.add_request(rid, ids, params,
                                              trace=trace)
                server._m_ingest.observe(
                    time.perf_counter() - self._t_request)
                server.loop.notify()

                if body.get("stream"):
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    self.send_header("Cache-Control", "no-cache")
                    self.end_headers()

                    def cb(text, index, steps=None):
                        # `steps`: a family whose step is a block says,
                        # one integer a token of this event, the count
                        # of passes the request had been given when the
                        # token was committed
                        delta = ({"role": "assistant", "content": text}
                                 if chat else None)
                        chunk = {
                            "id": "chunk", "object":
                                ("chat.completion.chunk" if chat
                                 else "text_completion"),
                            "created": created, "model": server.model_name,
                            "choices": [{
                                "index": index,
                                **({"delta": delta} if chat
                                   else {"text": text}),
                                **({} if steps is None
                                   else {"steps": steps}),
                                "finish_reason": None}],
                        }
                        self.wfile.write(
                            b"data: " + json.dumps(chunk).encode() + b"\n\n")
                        self.wfile.flush()

                    rid, out_ids, out_lps, reasons, _, _ = \
                        server._run_request(
                            ids, params, stream_cb=cb, stop_strs=stops,
                            disconnect_check=lambda:
                                _socket_disconnected(self.connection),
                            cancel_cb=lambda: server._cancelled.labels(
                                "stream").inc(),
                            rid=rid, seed_ids=pre_ids or None)
                    try:
                        if any(r == "migrated"
                               for r in reasons.values()):
                            # the sequence moved mid-stream: emit the
                            # resume marker and STOP — no [DONE], the
                            # router re-forwards to the target and the
                            # continuation rides the same client
                            # stream (serving/router.py _relay)
                            info = server._take_migrated_info(rid)
                            self.wfile.write(
                                b"data: " + json.dumps({"migrated": {
                                    "id": rid,
                                    "resume_id":
                                        info.get("resume_id"),
                                    "target": info.get("target"),
                                }}).encode() + b"\n\n")
                            self.wfile.flush()
                            return
                        self.wfile.write(b"data: [DONE]\n\n")
                        self.wfile.flush()
                    except OSError:
                        pass    # client left after the last delta
                    return

                rid, out_ids, out_lps, reasons, texts, errors = \
                    server._run_request(
                        ids, params, stop_strs=stops,
                        disconnect_check=lambda: _socket_disconnected(
                            self.connection),
                        cancel_cb=lambda: server._cancelled.labels(
                            "nonstream").inc(),
                        rid=rid, seed_ids=pre_ids or None)
                # robustness status mapping: a request that ran out of
                # time (its own deadline, or the drain window closing on
                # it) is a gateway timeout; a quarantined request is a
                # server error with the engine's structured diagnosis
                mig = [i for i, r in reasons.items()
                       if r == "migrated"]
                if mig:
                    # the sequence moved to another replica: hand the
                    # router what it needs to finish the request there
                    # (re-forward with X-Resume-Id) and stitch the
                    # partial output in front of the continuation
                    info = server._take_migrated_info(rid)
                    return self._json(200, {
                        "id": rid, "object": "migration",
                        "migrated": True,
                        "resume_id": info.get("resume_id"),
                        "target": info.get("target"),
                        "partial_text": texts.get(mig[0], ""),
                        "partial_tokens":
                            len(out_ids.get(mig[0], [])),
                    })
                timed_out = [r for r in reasons.values()
                             if r in _TIMEOUT_REASONS]
                if timed_out:
                    return self._json(504, {"error": {
                        "message": f"request timed out ({timed_out[0]})",
                        "type": "timeout", "code": 504,
                        "reason": timed_out[0], "id": rid}})
                if any(r == "error" for r in reasons.values()):
                    detail = next(iter(errors.values()), {})
                    return self._json(500, {"error": {
                        "message": "request failed in the engine",
                        "type": "engine_error", "code": 500,
                        "id": rid, **detail}})
                choices = []
                total_completion = 0
                for idx in sorted(out_ids):
                    toks = out_ids[idx]
                    total_completion += len(toks)
                    text = texts.get(idx, server._decode_text(toks))
                    choice = ({"index": idx, "message":
                               {"role": "assistant", "content": text},
                               "finish_reason": reasons.get(idx, "stop")}
                              if chat else
                              {"index": idx, "text": text,
                               "finish_reason": reasons.get(idx, "stop")})
                    lps = out_lps.get(idx)
                    if lps is not None and params.logprobs is not None:
                        # OpenAI completions logprobs block (token-id keyed
                        # when no tokenizer is attached)
                        def tname(t):
                            return (server._decode_text([t])
                                    if server.tokenizer else str(t))
                        choice["logprobs"] = {
                            "tokens": [tname(e.token_id) for e in lps],
                            "token_logprobs": [e.logprob for e in lps],
                            "top_logprobs": [
                                {tname(t): lp for t, lp in e.top}
                                for e in lps],
                        }
                    choices.append(choice)
                self._json(200, {
                    "id": rid,
                    "object": "chat.completion" if chat else "text_completion",
                    "created": created,
                    "model": server.model_name,
                    "choices": choices,
                    "usage": {
                        "prompt_tokens": len(ids),
                        "completion_tokens": total_completion,
                        "total_tokens": len(ids) + total_completion},
                })

        return Handler

    def serve(self, host: str = "127.0.0.1", port: int = 8000,
              background: bool = False) -> ThreadingHTTPServer:
        self._httpd = ThreadingHTTPServer((host, port), self.make_handler())
        startup_mark("listening", self.engine.registry)     # bound
        if background:
            t = threading.Thread(target=self._httpd.serve_forever,
                                 daemon=True)
            t.start()
        else:
            self._httpd.serve_forever()
        return self._httpd

    def begin_drain(self, timeout_sec: Optional[float] = None,
                    migrate_targets: Optional[List[str]] = None) -> None:
        """Graceful-drain entry point (the CLI's SIGTERM handler):
        admission stops (new requests get 503 + Retry-After), in-flight
        requests run to completion, and whatever outlives the drain
        window fails with 504. Poll `engine.drained` (or `wait_drained`)
        to know when it is safe to exit.

        When `migrate_targets` names healthy peers (the router's
        rolling restart and retirement pass them; the CLI SIGTERM
        handler reads $BIGDL_TPU_MIGRATE_TARGETS), in-flight mid-decode
        sequences are live-migrated there in a background thread while
        the drain settles — zero-loss, not merely zero-5xx."""
        self.engine.begin_drain(timeout_sec)
        self.loop.notify()       # wake the step loop to run the drain
        if migrate_targets and self.live_migration != "off":
            threading.Thread(
                target=self.migrate_out,
                args=(list(migrate_targets),), daemon=True).start()

    def wait_drained(self, poll_sec: float = 0.05) -> None:
        """Block until every in-flight request has finished (or the
        drain deadline failed it). Call after begin_drain()."""
        while not self.engine.drained:
            time.sleep(poll_sec)

    def shutdown(self):
        if self._httpd is not None:
            self._httpd.shutdown()
        self.loop.stop()


def main():
    """CLI: python -m bigdl_tpu.serving.api_server --model PATH [...]

    ``--tiny-random`` swaps the checkpoint for a seeded tiny random
    llama (utils/testing.tiny_random_model) — the replica mode the
    serving router's chaos tests and CPU bench lanes spawn: identical
    seeds give byte-identical weights across replicas, so a replayed
    greedy request must reproduce a dead replica's answer exactly."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--load-in-low-bit", default="sym_int4")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=2048)
    ap.add_argument("--embedder", default=None,
                    help="BERT checkpoint for /v1/embeddings")
    ap.add_argument("--tiny-random", action="store_true",
                    help="serve a seeded tiny random model instead of "
                         "a checkpoint (router tests / CPU bench)")
    ap.add_argument("--tiny-seed", type=int, default=0)
    ap.add_argument("--wedge-sec", type=float, default=10.0,
                    help="/health reports wedged past this step-loop "
                         "heartbeat age with work pending")
    ap.add_argument("--role", default=None, choices=list(REPLICA_ROLES),
                    help="fleet role (default $BIGDL_TPU_REPLICA_ROLE "
                         "or 'mixed'): prefill replicas ship KV to "
                         "decode replicas after chunked prefill")
    ap.add_argument("--kv-page-size", type=int, default=None,
                    help="positions per KV page (power of two; 0 = "
                         "per-slot slab; default "
                         "$BIGDL_TPU_KV_PAGE_SIZE or slab)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="paged-KV arena size in pages (0 = auto-size "
                         "to max_batch*max_seq; default "
                         "$BIGDL_TPU_KV_PAGES)")
    ap.add_argument("--prefix-sharing", default=None,
                    choices=["auto", "on", "off"],
                    help="radix-tree prompt-prefix page sharing for "
                         "the paged KV cache (default "
                         "$BIGDL_TPU_PREFIX_SHARING or auto)")
    args = ap.parse_args()
    role = resolve_replica_role(args.role)

    from bigdl_tpu.config import (default_kv_cache_dtype,
                                  enable_compilation_cache)

    enable_compilation_cache()
    tokenizer = None
    if args.tiny_random:
        from bigdl_tpu.utils.testing import tiny_random_model

        model = tiny_random_model(seed=args.tiny_seed)
        # the synthetic config's rope table caps the usable context
        args.max_seq = min(args.max_seq,
                           model.config.max_position_embeddings)
    else:
        if not args.model:
            ap.error("--model is required (or pass --tiny-random)")
        from bigdl_tpu.transformers.model import AutoModelForCausalLM

        model = AutoModelForCausalLM.from_pretrained(
            args.model, load_in_low_bit=args.load_in_low_bit,
            max_seq=args.max_seq)
        try:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(args.model)
        except Exception:
            pass

    from bigdl_tpu.serving.engine import EngineConfig

    # a prefill replica must keep prompt KV snapshots or it has
    # nothing to hand off; mixed/decode keep the host-DRAM-hungry
    # prefix cache off unless opted in elsewhere
    engine = LLMEngine(model, EngineConfig(
        max_batch=args.max_batch, max_seq=args.max_seq,
        # the dtype the load resolved from $BIGDL_TPU_KV_CACHE_DTYPE —
        # left at EngineConfig's default, the server cached in bf16
        # whatever the environment asked for
        kv_cache_dtype=getattr(model, "kv_cache_dtype", None)
        or default_kv_cache_dtype(),
        prefix_cache_entries=32 if role == "prefill" else 0,
        kv_page_size=args.kv_page_size, kv_pages=args.kv_pages,
        prefix_sharing=args.prefix_sharing))
    # span timelines name this process by its listen port, so the
    # router's merged /v1/trace/{id} view tells the replicas apart
    engine.spans.service = f"replica:{args.port}"
    embedder = embedder_tok = None
    if args.embedder:
        from transformers import AutoTokenizer

        from bigdl_tpu.transformers.embedder import BertEmbedder

        embedder = BertEmbedder.from_pretrained(args.embedder)
        embedder_tok = AutoTokenizer.from_pretrained(args.embedder)
    server = OpenAIServer(engine, tokenizer, embedder=embedder,
                          embedder_tokenizer=embedder_tok,
                          wedge_sec=args.wedge_sec, role=role)

    # SIGTERM (a deploy's kill) drains instead of dying: stop admitting
    # (503 + Retry-After), finish in-flight work up to
    # $BIGDL_TPU_DRAIN_TIMEOUT_SEC, then exit cleanly. Registered FIRST
    # so install_signal_dumps (below) chains to it after its postmortem.
    import signal as _signal

    def _drain_and_exit(signum, frame):
        # $BIGDL_TPU_MIGRATE_TARGETS (comma-separated host:port peers,
        # normally injected by the router/autoscaler at spawn): when
        # set, a SIGTERM drain live-migrates in-flight sequences there
        # instead of finishing them locally
        peers = [t.strip() for t in os.environ.get(
            "BIGDL_TPU_MIGRATE_TARGETS", "").split(",") if t.strip()]
        server.begin_drain(migrate_targets=peers or None)

        def _watch():
            server.wait_drained()
            server.shutdown()

        threading.Thread(target=_watch, daemon=True).start()

    _signal.signal(_signal.SIGTERM, _drain_and_exit)

    # operator kill (SIGTERM from a deploy, ^C) leaves a postmortem in
    # $BIGDL_TPU_POSTMORTEM_DIR before drain (SIGTERM) or default
    # termination (^C) proceeds
    from bigdl_tpu.observability.flight import install_signal_dumps

    install_signal_dumps(engine.write_postmortem)
    print(f"serving on http://{args.host}:{args.port}/v1")
    server.serve(args.host, args.port)
    server.loop.stop()


if __name__ == "__main__":
    main()
