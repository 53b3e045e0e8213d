"""Environment sanity check.

Equivalent of the reference's env-check scripts (reference
python/llm/scripts/env-check.sh + check.py and the `ipex-llm-init`
allocator/OMP setup — the TPU analog reports the XLA backend, device
inventory, memory, native-kernel availability, and key env flags).

Run: python -m bigdl_tpu.utils.env_check
"""

from __future__ import annotations

import difflib
import os
import sys

#: every knob the stack reads — the typo check suggests the nearest of
#: these for any unrecognized BIGDL_TPU_* variable (a misspelled knob
#: is silently ignored everywhere else, which is exactly the failure
#: mode an env check exists to catch)
KNOWN_ENV = (
    "BIGDL_TPU_AOT_TARGET",
    "BIGDL_TPU_ATTENTION_BACKEND",
    "BIGDL_TPU_AUTOSCALE_DWELL_SEC",
    "BIGDL_TPU_AUTOSCALE_MAX",
    "BIGDL_TPU_AUTOSCALE_MIN",
    "BIGDL_TPU_BROWNOUT_HIGH",
    "BIGDL_TPU_BROWNOUT_LOW",
    "BIGDL_TPU_CANARY_NLL_TOL",
    "BIGDL_TPU_CANARY_SEC",
    "BIGDL_TPU_COMPILE_MEMORY",
    "BIGDL_TPU_DECODE_RESIDENT",
    "BIGDL_TPU_DISABLE_NATIVE",
    "BIGDL_TPU_DRAIN_TIMEOUT_SEC",
    "BIGDL_TPU_EVENT_LOG",
    "BIGDL_TPU_EVENT_LOG_KEEP",
    "BIGDL_TPU_EVENT_LOG_MAX_BYTES",
    "BIGDL_TPU_FAULT_SPEC",
    "BIGDL_TPU_HANDOFF_RETRIES",
    "BIGDL_TPU_HANDOFF_TIMEOUT_MS",
    "BIGDL_TPU_HBM_BUDGET_FRACTION",
    "BIGDL_TPU_IQ_GRID_SOURCE",
    "BIGDL_TPU_KV_CACHE_DTYPE",
    "BIGDL_TPU_KV_PAGES",
    "BIGDL_TPU_KV_PAGE_SIZE",
    "BIGDL_TPU_LIVE_MIGRATION",
    "BIGDL_TPU_MATMUL_BACKEND",
    "BIGDL_TPU_MAX_QUEUE_BYTES",
    "BIGDL_TPU_MAX_QUEUE_DEPTH",
    "BIGDL_TPU_MAX_SEQ",
    "BIGDL_TPU_MEMORY_POLL_SEC",
    "BIGDL_TPU_MIGRATE_MAX_BYTES",
    "BIGDL_TPU_MIGRATE_TARGETS",
    "BIGDL_TPU_MIGRATE_TIMEOUT_MS",
    "BIGDL_TPU_MOE_DISPATCH",
    "BIGDL_TPU_NATIVE_CACHE",
    "BIGDL_TPU_PERF_HISTORY",
    "BIGDL_TPU_POSTMORTEM_DIR",
    "BIGDL_TPU_PREFIX_SHARING",
    "BIGDL_TPU_PREPACK",
    "BIGDL_TPU_PROFILER_DIR_CAP_BYTES",
    "BIGDL_TPU_PROFILER_MAX_SEC",
    "BIGDL_TPU_QOS_AGING_SEC",
    "BIGDL_TPU_QOS_DEFAULT",
    "BIGDL_TPU_QUALITY",
    "BIGDL_TPU_QUALITY_HISTORY",
    "BIGDL_TPU_QUALITY_PROBE_STEPS",
    "BIGDL_TPU_QUALITY_RECOVER_STEPS",
    "BIGDL_TPU_QUALITY_THRESHOLD",
    "BIGDL_TPU_QUALITY_TRIP_STEPS",
    "BIGDL_TPU_QUANTIZE_KV_CACHE",
    "BIGDL_TPU_RECOMPILE_WARN",
    "BIGDL_TPU_REPLICA_ROLE",
    "BIGDL_TPU_REQUEST_DEADLINE_MS",
    "BIGDL_TPU_ROUTER_CRASH_BUDGET",
    "BIGDL_TPU_ROUTER_HEALTH_SEC",
    "BIGDL_TPU_ROUTER_HEDGE_MS",
    "BIGDL_TPU_ROUTER_JOURNAL",
    "BIGDL_TPU_ROUTER_REPLICAS",
    "BIGDL_TPU_SENTINEL",
    "BIGDL_TPU_SENTINEL_RECOVER_STEPS",
    "BIGDL_TPU_SENTINEL_THRESHOLD",
    "BIGDL_TPU_SENTINEL_TRIP_STEPS",
    "BIGDL_TPU_SLO_ALERT_LOG",
    "BIGDL_TPU_SLO_SPEC",
    "BIGDL_TPU_TENANT_BURST",
    "BIGDL_TPU_TENANT_RPS",
    "BIGDL_TPU_TENANT_TPS",
    "BIGDL_TPU_TRACE_SAMPLE",
    "BIGDL_TPU_USAGE_LOG",
)


def find_env_typos(environ=None) -> list:
    """Unrecognized ``BIGDL_TPU_*`` variables with a close known knob:
    ``[{"unknown": ..., "did_you_mean": ...}]``. High match cutoff so
    unrelated private variables don't false-positive."""
    env = os.environ if environ is None else environ
    typos = []
    for k in sorted(env):
        if not k.startswith("BIGDL_TPU_") or k in KNOWN_ENV:
            continue
        close = difflib.get_close_matches(k, KNOWN_ENV, n=1, cutoff=0.85)
        if close:
            typos.append({"unknown": k, "did_you_mean": close[0]})
    return typos


def collect() -> dict:
    info: dict = {"python": sys.version.split()[0]}
    try:
        import jax

        info["jax"] = jax.__version__
        info["backend"] = jax.default_backend()
        devs = jax.devices()
        info["devices"] = [str(d) for d in devs]
        try:
            stats = devs[0].memory_stats() or {}
            lim = stats.get("bytes_limit")
            if lim:
                info["device_memory_gb"] = round(lim / 2**30, 2)
        except Exception:
            pass
    except Exception as e:  # pragma: no cover
        info["jax_error"] = repr(e)

    try:
        from bigdl_tpu import __version__, native

        info["bigdl_tpu"] = __version__
        info["native_kernels"] = native.get_lib() is not None
    except Exception as e:
        info["bigdl_tpu_error"] = repr(e)

    for mod in ("flax", "optax", "transformers", "safetensors"):
        try:
            info[mod] = __import__(mod).__version__
        except Exception:
            info[mod] = None

    info["env"] = {k: v for k, v in os.environ.items()
                   if k.startswith(("JAX_", "XLA_", "BIGDL_", "LIBTPU"))}

    # observability event log (serving request tracer JSONL sink):
    # report up front whether the configured path is actually writable —
    # the tracer itself degrades silently by design
    ev = os.environ.get("BIGDL_TPU_EVENT_LOG")
    if ev:
        from bigdl_tpu.observability.tracing import validate_event_log_path

        info["event_log"] = validate_event_log_path(ev)

    # event-log rotation limit: the tracer degrades to unbounded on a
    # bad value, so report it here where an operator will see it
    evmax = os.environ.get("BIGDL_TPU_EVENT_LOG_MAX_BYTES")
    if evmax:
        from bigdl_tpu.observability.tracing import \
            resolve_event_log_max_bytes

        try:
            info["event_log_max_bytes"] = {
                "value": resolve_event_log_max_bytes(evmax), "valid": True}
        except ValueError as e:
            info["event_log_max_bytes"] = {
                "value": evmax, "valid": False, "error": str(e)}

    # rotated-file retention: the tracer and the span sink both degrade
    # to keep=1 on a bad value, so surface it here
    evkeep = os.environ.get("BIGDL_TPU_EVENT_LOG_KEEP")
    if evkeep:
        from bigdl_tpu.observability.tracing import \
            resolve_event_log_keep

        try:
            info["event_log_keep"] = {
                "value": resolve_event_log_keep(evkeep), "valid": True}
        except ValueError as e:
            info["event_log_keep"] = {
                "value": evkeep, "valid": False, "error": str(e)}

    # distributed-trace tail sampling: the span recorder degrades to
    # 1.0 (record everything) on a bad value
    tsample = os.environ.get("BIGDL_TPU_TRACE_SAMPLE")
    if tsample:
        from bigdl_tpu.observability.disttrace import \
            resolve_trace_sample

        try:
            info["trace_sample"] = {
                "value": resolve_trace_sample(tsample), "valid": True}
        except ValueError as e:
            info["trace_sample"] = {
                "value": tsample, "valid": False, "error": str(e)}

    # postmortem dump directory: write_postmortem swallows failures by
    # contract, so an unwritable dir would otherwise only show up as a
    # missing dump after a crash
    pm = os.environ.get("BIGDL_TPU_POSTMORTEM_DIR")
    if pm:
        from bigdl_tpu.observability.flight import validate_postmortem_dir

        info["postmortem_dir"] = validate_postmortem_dir(pm)

    # recompile-storm warning threshold (compile_watch falls back to the
    # default on a bad value; surface it here instead)
    rw = os.environ.get("BIGDL_TPU_RECOMPILE_WARN")
    if rw:
        from bigdl_tpu.observability.compile_watch import \
            resolve_recompile_threshold

        try:
            info["recompile_warn"] = {
                "value": resolve_recompile_threshold(rw), "valid": True}
        except ValueError as e:
            info["recompile_warn"] = {
                "value": rw, "valid": False, "error": str(e)}

    # per-compile memory analysis: off unless asked for (default off
    # since PR 55: the AOT capture can lower and compile or load every
    # program a second time on the way to readiness); say what a set
    # value resolves to
    cm = os.environ.get("BIGDL_TPU_COMPILE_MEMORY")
    if cm:
        from bigdl_tpu.observability.compile_watch import \
            memory_capture_enabled

        info["compile_memory"] = {"value": cm,
                                  "enabled": memory_capture_enabled()}

    # HBM admission budget fraction (the memory ledger falls back to
    # the default on a bad value; surface it here instead)
    bf = os.environ.get("BIGDL_TPU_HBM_BUDGET_FRACTION")
    if bf:
        from bigdl_tpu.observability.memory import \
            resolve_hbm_budget_fraction

        try:
            info["hbm_budget_fraction"] = {
                "value": resolve_hbm_budget_fraction(bf), "valid": True}
        except ValueError as e:
            info["hbm_budget_fraction"] = {
                "value": bf, "valid": False, "error": str(e)}

    # live memory_stats poll throttle (same fallback contract)
    mp = os.environ.get("BIGDL_TPU_MEMORY_POLL_SEC")
    if mp:
        from bigdl_tpu.observability.memory import resolve_memory_poll_sec

        try:
            info["memory_poll_sec"] = {
                "value": resolve_memory_poll_sec(mp), "valid": True}
        except ValueError as e:
            info["memory_poll_sec"] = {
                "value": mp, "valid": False, "error": str(e)}

    # KV cache storage dtype: fail loudly here rather than at the first
    # model load (a typo'd dtype name otherwise surfaces deep in
    # init_cache)
    kvd = os.environ.get("BIGDL_TPU_KV_CACHE_DTYPE")
    if kvd:
        from bigdl_tpu.ops.kvcache import (KV_CACHE_DTYPES,
                                           resolve_kv_cache_dtype)

        try:
            info["kv_cache_dtype"] = {
                "value": resolve_kv_cache_dtype(kvd), "valid": True}
        except ValueError:
            info["kv_cache_dtype"] = {
                "value": kvd, "valid": False,
                "choices": sorted(KV_CACHE_DTYPES)}

    # decode fast-path tristates (config.py from_env falls back to
    # "auto" on a bad value; surface the typo here instead): resident
    # single-dispatch decode and load-time weight prepack
    tristate_knobs = (
        ("decode_resident", "BIGDL_TPU_DECODE_RESIDENT",
         "resolve_decode_resident"),
        ("prepack", "BIGDL_TPU_PREPACK", "resolve_prepack"),
        ("sentinel", "BIGDL_TPU_SENTINEL", "resolve_sentinel"),
        ("quality", "BIGDL_TPU_QUALITY", "resolve_quality"),
        ("prefix_sharing", "BIGDL_TPU_PREFIX_SHARING",
         "resolve_prefix_sharing"),
        # paged-KV geometry (not tristates, but the same config.py
        # silently-fall-back contract: a typo'd page size means the
        # engine quietly runs the per-slot slab instead)
        ("kv_page_size", "BIGDL_TPU_KV_PAGE_SIZE",
         "resolve_kv_page_size"),
        ("kv_pages", "BIGDL_TPU_KV_PAGES", "resolve_kv_pages"),
    )
    for key, envname, fname in tristate_knobs:
        raw = os.environ.get(envname)
        if not raw:
            continue
        from bigdl_tpu import config as _config

        try:
            info[key] = {"value": getattr(_config, fname)(raw),
                         "valid": True}
        except ValueError as e:
            info[key] = {"value": raw, "valid": False, "error": str(e)}

    # perf-history baseline sink (the sentinel degrades to a live
    # baseline if the file is unwritable — report it up front, same
    # contract as the event log)
    ph = os.environ.get("BIGDL_TPU_PERF_HISTORY")
    if ph:
        from bigdl_tpu.observability.sentinel import \
            validate_perf_history_path

        info["perf_history"] = validate_perf_history_path(ph)

    # perf-regression sentinel tuning (the sentinel falls back to
    # defaults on bad values; surface range errors here instead)
    sentinel_knobs = (
        ("sentinel_threshold", "BIGDL_TPU_SENTINEL_THRESHOLD",
         "resolve_sentinel_threshold"),
        ("sentinel_trip_steps", "BIGDL_TPU_SENTINEL_TRIP_STEPS",
         "resolve_sentinel_trip_steps"),
        ("sentinel_recover_steps", "BIGDL_TPU_SENTINEL_RECOVER_STEPS",
         "resolve_sentinel_recover_steps"),
    )
    for key, envname, fname in sentinel_knobs:
        raw = os.environ.get(envname)
        if not raw:
            continue
        from bigdl_tpu.observability import sentinel as _sentinel

        try:
            info[key] = {"value": getattr(_sentinel, fname)(raw),
                         "valid": True}
        except ValueError as e:
            info[key] = {"value": raw, "valid": False, "error": str(e)}

    # quality-history baseline sink (same degrade-to-live contract as
    # the perf history)
    qh = os.environ.get("BIGDL_TPU_QUALITY_HISTORY")
    if qh:
        from bigdl_tpu.observability.quality import \
            validate_quality_history_path

        info["quality_history"] = validate_quality_history_path(qh)

    # QualitySentinel tuning + the golden-probe period (the sentinel
    # falls back to defaults on bad values; surface range errors here)
    quality_knobs = (
        ("quality_threshold", "BIGDL_TPU_QUALITY_THRESHOLD",
         "resolve_quality_threshold"),
        ("quality_trip_steps", "BIGDL_TPU_QUALITY_TRIP_STEPS",
         "resolve_quality_trip_steps"),
        ("quality_recover_steps", "BIGDL_TPU_QUALITY_RECOVER_STEPS",
         "resolve_quality_recover_steps"),
        ("quality_probe_steps", "BIGDL_TPU_QUALITY_PROBE_STEPS",
         "resolve_quality_probe_steps"),
    )
    for key, envname, fname in quality_knobs:
        raw = os.environ.get(envname)
        if not raw:
            continue
        from bigdl_tpu.observability import quality as _quality

        try:
            info[key] = {"value": getattr(_quality, fname)(raw),
                         "valid": True}
        except ValueError as e:
            info[key] = {"value": raw, "valid": False, "error": str(e)}

    # profiler capture time-box (start_profiler refuses to start on a
    # bad value, but an operator wants to know before the incident)
    pms = os.environ.get("BIGDL_TPU_PROFILER_MAX_SEC")
    if pms:
        from bigdl_tpu.utils.profiling import resolve_profiler_max_sec

        try:
            info["profiler_max_sec"] = {
                "value": resolve_profiler_max_sec(pms), "valid": True}
        except ValueError as e:
            info["profiler_max_sec"] = {
                "value": pms, "valid": False, "error": str(e)}

    # fault-injection spec: a typo'd spec silently injecting nothing
    # would make a chaos run vacuously green — fail the check instead
    fs = os.environ.get("BIGDL_TPU_FAULT_SPEC")
    if fs:
        from bigdl_tpu.robustness.faults import validate_fault_spec

        info["fault_spec"] = validate_fault_spec(fs)

    # default per-request deadline (the engine falls back to NO deadline
    # on a bad value; surface it here instead)
    dl = os.environ.get("BIGDL_TPU_REQUEST_DEADLINE_MS")
    if dl:
        from bigdl_tpu.robustness import resolve_request_deadline_ms

        try:
            info["request_deadline_ms"] = {
                "value": resolve_request_deadline_ms(dl), "valid": True}
        except ValueError as e:
            info["request_deadline_ms"] = {
                "value": dl, "valid": False, "error": str(e)}

    # graceful-drain window (engine falls back to the 30 s default)
    dt = os.environ.get("BIGDL_TPU_DRAIN_TIMEOUT_SEC")
    if dt:
        from bigdl_tpu.robustness import resolve_drain_timeout_sec

        try:
            info["drain_timeout_sec"] = {
                "value": resolve_drain_timeout_sec(dt), "valid": True}
        except ValueError as e:
            info["drain_timeout_sec"] = {
                "value": dt, "valid": False, "error": str(e)}

    # serving-router knobs (the router falls back to defaults on bad
    # values; surface range errors here instead)
    router_knobs = (
        ("router_health_sec", "BIGDL_TPU_ROUTER_HEALTH_SEC",
         "resolve_router_health_sec"),
        ("router_replicas", "BIGDL_TPU_ROUTER_REPLICAS",
         "resolve_router_replicas"),
        ("router_hedge_ms", "BIGDL_TPU_ROUTER_HEDGE_MS",
         "resolve_router_hedge_ms"),
        ("router_crash_budget", "BIGDL_TPU_ROUTER_CRASH_BUDGET",
         "resolve_router_crash_budget"),
    )
    for key, envname, fname in router_knobs:
        raw = os.environ.get(envname)
        if not raw:
            continue
        from bigdl_tpu.serving import router as _router

        try:
            info[key] = {"value": getattr(_router, fname)(raw),
                         "valid": True}
        except ValueError as e:
            info[key] = {"value": raw, "valid": False, "error": str(e)}

    # overload-control knobs (QoS / per-tenant limits / bounded queue /
    # brownout thresholds): the engine falls back to defaults on bad
    # values, so range errors surface here instead
    overload_knobs = (
        ("qos_default", "BIGDL_TPU_QOS_DEFAULT", "resolve_qos_default"),
        ("qos_aging_sec", "BIGDL_TPU_QOS_AGING_SEC",
         "resolve_qos_aging_sec"),
        ("tenant_rps", "BIGDL_TPU_TENANT_RPS", "resolve_tenant_rps"),
        ("tenant_tps", "BIGDL_TPU_TENANT_TPS", "resolve_tenant_tps"),
        ("tenant_burst", "BIGDL_TPU_TENANT_BURST",
         "resolve_tenant_burst"),
        ("brownout_high", "BIGDL_TPU_BROWNOUT_HIGH",
         "resolve_brownout_high"),
        ("brownout_low", "BIGDL_TPU_BROWNOUT_LOW",
         "resolve_brownout_low"),
        ("max_queue_depth", "BIGDL_TPU_MAX_QUEUE_DEPTH",
         "resolve_max_queue_depth"),
        ("max_queue_bytes", "BIGDL_TPU_MAX_QUEUE_BYTES",
         "resolve_max_queue_bytes"),
    )
    for key, envname, fname in overload_knobs:
        raw = os.environ.get(envname)
        if not raw:
            continue
        from bigdl_tpu.serving import overload as _overload

        try:
            info[key] = {"value": getattr(_overload, fname)(raw),
                         "valid": True}
        except ValueError as e:
            info[key] = {"value": raw, "valid": False, "error": str(e)}

    # fleet autoscaler bounds + dwell (the autoscaler falls back to
    # defaults on bad values; surface range errors here instead)
    autoscale_knobs = (
        ("autoscale_min", "BIGDL_TPU_AUTOSCALE_MIN",
         "resolve_autoscale_min"),
        ("autoscale_max", "BIGDL_TPU_AUTOSCALE_MAX",
         "resolve_autoscale_max"),
        ("autoscale_dwell_sec", "BIGDL_TPU_AUTOSCALE_DWELL_SEC",
         "resolve_autoscale_dwell_sec"),
    )
    for key, envname, fname in autoscale_knobs:
        raw = os.environ.get(envname)
        if not raw:
            continue
        from bigdl_tpu.serving import autoscaler as _autoscaler

        try:
            info[key] = {"value": getattr(_autoscaler, fname)(raw),
                         "valid": True}
        except ValueError as e:
            info[key] = {"value": raw, "valid": False, "error": str(e)}

    # KV-handoff transfer knobs + replica role (the api server refuses
    # to start on a bad role, but a typo'd timeout/retry count would
    # silently fall back — report both classes here)
    handoff_knobs = (
        ("replica_role", "BIGDL_TPU_REPLICA_ROLE",
         "resolve_replica_role"),
        ("handoff_timeout_ms", "BIGDL_TPU_HANDOFF_TIMEOUT_MS",
         "resolve_handoff_timeout_ms"),
        ("handoff_retries", "BIGDL_TPU_HANDOFF_RETRIES",
         "resolve_handoff_retries"),
    )
    for key, envname, fname in handoff_knobs:
        raw = os.environ.get(envname)
        if not raw:
            continue
        from bigdl_tpu.serving import api_server as _api_server

        try:
            info[key] = {"value": getattr(_api_server, fname)(raw),
                         "valid": True}
        except ValueError as e:
            info[key] = {"value": raw, "valid": False, "error": str(e)}

    # live-migration knobs (the api server falls back to defaults on a
    # bad timeout/size and refuses to start on a bad mode; the router
    # treats an unusable journal path as journal-off — all four classes
    # of typo get reported here instead of surfacing mid-drain)
    migrate_knobs = (
        ("live_migration", "BIGDL_TPU_LIVE_MIGRATION",
         "resolve_live_migration"),
        ("migrate_timeout_ms", "BIGDL_TPU_MIGRATE_TIMEOUT_MS",
         "resolve_migrate_timeout_ms"),
        ("migrate_max_bytes", "BIGDL_TPU_MIGRATE_MAX_BYTES",
         "resolve_migrate_max_bytes"),
    )
    for key, envname, fname in migrate_knobs:
        raw = os.environ.get(envname)
        if not raw:
            continue
        from bigdl_tpu.serving import api_server as _api_server

        try:
            info[key] = {"value": getattr(_api_server, fname)(raw),
                         "valid": True}
        except ValueError as e:
            info[key] = {"value": raw, "valid": False, "error": str(e)}

    # migrate-out peer list: free-form host:port entries, so just check
    # the shape — a malformed entry silently skips that peer at drain
    # time, which is the worst moment to learn about a typo
    mt = os.environ.get("BIGDL_TPU_MIGRATE_TARGETS")
    if mt:
        bad = []
        for t in (x.strip() for x in mt.split(",")):
            if not t:
                continue
            host, _, port = t.rpartition(":")
            if not host or not port.isdigit():
                bad.append(t)
        info["migrate_targets"] = (
            {"value": mt, "valid": True} if not bad else
            {"value": mt, "valid": False,
             "error": f"malformed host:port entries: {bad}"})

    # durable router journal path (the router degrades to in-memory on
    # a relative path or an unwritable file)
    rj = os.environ.get("BIGDL_TPU_ROUTER_JOURNAL")
    if rj:
        from bigdl_tpu.serving.router import resolve_router_journal

        try:
            resolved = resolve_router_journal(rj)
            writable = True
            err = None
            d = os.path.dirname(resolved) or "/"
            if not os.path.isdir(d):
                writable, err = False, f"directory does not exist: {d}"
            elif not os.access(d, os.W_OK):
                writable, err = False, f"directory not writable: {d}"
            info["router_journal"] = {"value": resolved,
                                      "valid": True, "writable": writable}
            if err:
                info["router_journal"]["error"] = err
        except ValueError as e:
            info["router_journal"] = {"value": rj, "valid": False,
                                      "error": str(e)}

    # fleet SLO engine / usage metering / canary probes: the tracker
    # swallows a bad spec (falls back to defaults) and the prober
    # treats a bad interval as off, so this is where a broken override
    # actually gets reported
    slo_spec = os.environ.get("BIGDL_TPU_SLO_SPEC")
    if slo_spec:
        from bigdl_tpu.observability.slo import resolve_slo_spec

        try:
            info["slo_spec"] = {"value": resolve_slo_spec(slo_spec),
                                "valid": True}
        except ValueError as e:
            info["slo_spec"] = {"value": slo_spec, "valid": False,
                                "error": str(e)}
    slo_log = os.environ.get("BIGDL_TPU_SLO_ALERT_LOG")
    if slo_log:
        from bigdl_tpu.observability.slo import \
            validate_slo_alert_log_path

        info["slo_alert_log"] = validate_slo_alert_log_path(slo_log)
    usage_log = os.environ.get("BIGDL_TPU_USAGE_LOG")
    if usage_log:
        from bigdl_tpu.observability.usage import \
            validate_usage_log_path

        info["usage_log"] = validate_usage_log_path(usage_log)
    canary_sec = os.environ.get("BIGDL_TPU_CANARY_SEC")
    if canary_sec:
        from bigdl_tpu.serving.canary import resolve_canary_sec

        try:
            info["canary_sec"] = {
                "value": resolve_canary_sec(canary_sec), "valid": True}
        except ValueError as e:
            info["canary_sec"] = {"value": canary_sec, "valid": False,
                                  "error": str(e)}

    # canary NLL-tolerance mode (the prober falls back to byte-equality
    # only on a bad value; surface it here instead)
    nll_tol = os.environ.get("BIGDL_TPU_CANARY_NLL_TOL")
    if nll_tol:
        from bigdl_tpu.serving.canary import resolve_canary_nll_tol

        try:
            info["canary_nll_tol"] = {
                "value": resolve_canary_nll_tol(nll_tol), "valid": True}
        except ValueError as e:
            info["canary_nll_tol"] = {"value": nll_tol, "valid": False,
                                      "error": str(e)}

    typos = find_env_typos()
    if typos:
        info["env_typos"] = typos
    return info


def main() -> int:
    info = collect()
    width = max(len(k) for k in info)
    for k, v in info.items():
        if k == "env":
            print("env flags:")
            for ek, ev in sorted(v.items()):
                print(f"  {ek}={ev}")
        else:
            print(f"{k:<{width}} : {v}")
    ok = ("jax_error" not in info and "bigdl_tpu_error" not in info
          and info.get("kv_cache_dtype", {}).get("valid", True)
          and info.get("event_log_max_bytes", {}).get("valid", True)
          and info.get("event_log_keep", {}).get("valid", True)
          and info.get("trace_sample", {}).get("valid", True)
          and info.get("recompile_warn", {}).get("valid", True)
          and info.get("hbm_budget_fraction", {}).get("valid", True)
          and info.get("memory_poll_sec", {}).get("valid", True)
          and info.get("decode_resident", {}).get("valid", True)
          and info.get("prepack", {}).get("valid", True)
          and info.get("sentinel", {}).get("valid", True)
          and info.get("prefix_sharing", {}).get("valid", True)
          and info.get("kv_page_size", {}).get("valid", True)
          and info.get("kv_pages", {}).get("valid", True)
          and info.get("sentinel_threshold", {}).get("valid", True)
          and info.get("sentinel_trip_steps", {}).get("valid", True)
          and info.get("sentinel_recover_steps", {}).get("valid", True)
          and info.get("profiler_max_sec", {}).get("valid", True)
          and info.get("perf_history", {}).get("writable", True)
          and info.get("fault_spec", {}).get("valid", True)
          and info.get("request_deadline_ms", {}).get("valid", True)
          and info.get("drain_timeout_sec", {}).get("valid", True)
          and info.get("router_health_sec", {}).get("valid", True)
          and info.get("router_replicas", {}).get("valid", True)
          and info.get("router_hedge_ms", {}).get("valid", True)
          and info.get("router_crash_budget", {}).get("valid", True)
          and info.get("qos_default", {}).get("valid", True)
          and info.get("qos_aging_sec", {}).get("valid", True)
          and info.get("tenant_rps", {}).get("valid", True)
          and info.get("tenant_tps", {}).get("valid", True)
          and info.get("tenant_burst", {}).get("valid", True)
          and info.get("brownout_high", {}).get("valid", True)
          and info.get("brownout_low", {}).get("valid", True)
          and info.get("max_queue_depth", {}).get("valid", True)
          and info.get("max_queue_bytes", {}).get("valid", True)
          and info.get("autoscale_min", {}).get("valid", True)
          and info.get("autoscale_max", {}).get("valid", True)
          and info.get("autoscale_dwell_sec", {}).get("valid", True)
          and info.get("replica_role", {}).get("valid", True)
          and info.get("handoff_timeout_ms", {}).get("valid", True)
          and info.get("handoff_retries", {}).get("valid", True)
          and info.get("live_migration", {}).get("valid", True)
          and info.get("migrate_timeout_ms", {}).get("valid", True)
          and info.get("migrate_max_bytes", {}).get("valid", True)
          and info.get("migrate_targets", {}).get("valid", True)
          and info.get("router_journal", {}).get("valid", True)
          and info.get("router_journal", {}).get("writable", True)
          and info.get("slo_spec", {}).get("valid", True)
          and info.get("canary_sec", {}).get("valid", True)
          and info.get("canary_nll_tol", {}).get("valid", True)
          and info.get("quality", {}).get("valid", True)
          and info.get("quality_threshold", {}).get("valid", True)
          and info.get("quality_trip_steps", {}).get("valid", True)
          and info.get("quality_recover_steps", {}).get("valid", True)
          and info.get("quality_probe_steps", {}).get("valid", True)
          and info.get("quality_history", {}).get("writable", True)
          and info.get("slo_alert_log", {}).get("writable", True)
          and info.get("usage_log", {}).get("writable", True)
          and not info.get("env_typos")
          and info.get("postmortem_dir", {}).get("writable", True))
    print("status :", "OK" if ok else "PROBLEMS FOUND")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
