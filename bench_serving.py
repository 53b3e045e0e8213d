"""Serving-engine throughput benchmark: continuous batching on one chip.

The reference's serving claim is its vLLM port (continuous batching,
`/root/reference/python/llm/src/ipex_llm/vllm/`); this measures the
analog here: aggregate generated tokens/s through `LLMEngine.step()`
with every slot busy — prefill admission, batched decode, and the
on-device sampler all on the hot path.

llama2-7B INT4, max_batch 8, 128-token prompts, 64 new tokens per
request, 24 requests (3 full waves). Needs a TPU: without one it prints
`{"ok": false, ...}` and exits non-zero. Prints ONE JSON line like
bench.py.

Physics ceiling: a batch-B decode step still reads the packed weights
once, so tokens/s <= B / (weight_bytes / HBM_BW). Reported numbers
above that ceiling mean the runtime did not execute (same poisoned-
buffer guard as bench.py).
"""

from __future__ import annotations

import json
import os
import sys
import time


def _parse_replicas(argv: "list[str]") -> "int | None":
    """``--replicas N`` -> replica count for the router lane."""
    for i, a in enumerate(argv):
        if a == "--replicas" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--replicas="):
            return int(a.split("=", 1)[1])
    return None


def run_router_bench(n_replicas: int, n_requests: int = 16,
                     new_tokens: int = 8, prompt_len: int = 12) -> dict:
    """Drive a threaded completion wave through the multi-replica
    router (tiny-random CPU replicas, byte-identical weights) and
    report aggregate throughput plus the router's own stats block
    (failovers / replays / breaker trips — the counters bench_diff
    gates lower-is-better). ``$BIGDL_TPU_FAULT_SPEC`` inherits into
    the replicas, so a chaos run is the same command plus the spec."""
    import threading
    import urllib.request

    import numpy as np

    from bigdl_tpu.serving.router import Router, RouterConfig

    cmd = [sys.executable, "-m", "bigdl_tpu.serving.api_server",
           "--tiny-random", "--host", "127.0.0.1", "--port", "{port}",
           "--max-batch", "4", "--max-seq", "64"]
    # replicas on CPU always: the router lane measures the tier, not
    # the chip, and N processes grabbing an exclusive-access TPU would
    # starve each other. Canary on: byte-identical seeded replicas must
    # record zero mismatches on a clean run (bench_diff zero-gates
    # router.counters.canary_failures)
    router = Router(replica_cmd=cmd,
                    config=RouterConfig(replicas=n_replicas,
                                        health_sec=0.25,
                                        canary_sec=0.5),
                    spawn_env={"JAX_PLATFORMS": "cpu"})
    router.start()
    httpd = router.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, prompt_len).tolist()
               for _ in range(n_requests)]
    results: list = []
    lock = threading.Lock()

    def one(i: int) -> None:
        body = json.dumps({"prompt": prompts[i],
                           "max_tokens": new_tokens}).encode()
        try:
            req = urllib.request.Request(
                base + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                doc = json.loads(resp.read())
            toks = doc.get("usage", {}).get("completion_tokens", 0)
            with lock:
                results.append(("ok", toks))
        except Exception as e:
            with lock:
                results.append(("error", f"{type(e).__name__}: {e}"))

    try:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        with urllib.request.urlopen(base + "/v1/router/stats",
                                    timeout=10) as resp:
            stats = json.loads(resp.read())
    finally:
        httpd.shutdown()
        router.shutdown()
    done = sum(1 for s, _ in results if s == "ok")
    generated = sum(t for s, t in results if s == "ok")
    return {
        "replicas": n_replicas,
        "n_requests": n_requests,
        "completed": int(done),
        "generated_tokens": int(generated),
        "wall_s": round(wall, 2),
        "tokens_per_s": round(generated / max(wall, 1e-9), 1),
        "errors": [m for s, m in results if s == "error"][:5],
        # GET /v1/router/stats embedded like the engine's memory /
        # compile blocks: per-replica state + failover/replay/breaker
        # counters ride along in the bench JSON
        "router": stats,
    }


def run_restart_bench(n_replicas: int = 2, new_tokens: int = 16,
                      prompt_len: int = 12, workers: int = 4) -> dict:
    """Rolling-restart-under-load lane (ISSUE 20 acceptance): worker
    threads hammer the fleet with buffered completions while
    ``rolling_restart`` drains + respawns every replica. Live
    migration means the drain ships each in-flight sequence's KV to a
    peer instead of replaying it, so the gated rows are
    ``http_5xx == 0`` (zero-loss) and ``recomputed_tokens_total == 0``
    (zero *recompute* — journal replays would burn decode steps the
    fleet already paid for); ``migrated_tokens_total`` reports how
    many tokens the handoffs actually saved."""
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    from bigdl_tpu.serving.router import Router, RouterConfig

    cmd = [sys.executable, "-m", "bigdl_tpu.serving.api_server",
           "--tiny-random", "--tiny-seed", "7",
           "--host", "127.0.0.1", "--port", "{port}",
           "--max-batch", "4", "--max-seq", "64"]
    router = Router(replica_cmd=cmd,
                    config=RouterConfig(replicas=n_replicas,
                                        health_sec=0.25),
                    spawn_env={"JAX_PLATFORMS": "cpu"})
    router.start()
    httpd = router.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, prompt_len).tolist()
               for _ in range(workers)]
    stop = threading.Event()
    lock = threading.Lock()
    statuses: list = []

    def pound(i: int) -> None:
        body = json.dumps({"prompt": prompts[i],
                           "max_tokens": new_tokens}).encode()
        while not stop.is_set():
            req = urllib.request.Request(
                base + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=300) as resp:
                    json.loads(resp.read())
                st = 200
            except urllib.error.HTTPError as e:
                st = e.code
            except Exception as e:
                st = f"{type(e).__name__}"
            with lock:
                statuses.append(st)

    out: dict = {"replicas": n_replicas}
    try:
        threads = [threading.Thread(target=pound, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        time.sleep(1.0)        # load established before the restart
        t0 = time.perf_counter()
        with router._admin_lock:
            summary = router.rolling_restart()
        out["restart_wall_s"] = round(time.perf_counter() - t0, 2)
        out["restart_ok"] = bool(summary.get("ok"))
        time.sleep(3 * 0.25 + 0.5)   # final stats polls land
    finally:
        stop.set()
        for t in threads:
            t.join()
        snap = router.stats_snapshot()
        httpd.shutdown()
        router.shutdown()
    cnt = snap["counters"]
    out.update({
        "requests_total": len(statuses),
        "completed": statuses.count(200),
        # the zero-loss gate: ANY 5xx during a planned restart is a
        # regression (bench_diff flags growth from zero as inf%)
        "http_5xx": sum(1 for s in statuses
                        if isinstance(s, int) and s >= 500),
        "transport_errors": sum(1 for s in statuses
                                if not isinstance(s, int)),
        "sequences_migrated": int(cnt.get("sequences_migrated", 0)),
        "migrated_tokens_total": int(cnt.get("migrated_tokens_total", 0)),
        # the zero-recompute gate: journal replays re-decode tokens the
        # fleet already generated; live migration must keep this at 0
        "recomputed_tokens_total": int(
            cnt.get("recomputed_tokens_total", 0)),
        "migrations_failed": int(cnt.get("migration_failed", 0)
                                 + cnt.get("sequences_migrate_failed", 0)),
        "migration": snap.get("migration"),
        "journal": snap.get("journal"),
    })
    return out


def run_autoscale_bench(n_replicas: int = 2, n_requests: int = 12,
                        new_tokens: int = 8, prompt_len: int = 12) -> dict:
    """Forced-scale-down recovery lane: burst at <=1x on the full
    fleet (zero shed expected), forcibly retire one replica, then let
    the autoscaler observe the pressure of a second burst and spawn
    the replacement. The final burst's ``shed_total`` (gated by
    bench_diff, lower-is-better — any growth past zero flags) proves
    the fleet is back to zero-shed at the same offered load."""
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    from bigdl_tpu.serving.autoscaler import Autoscaler, AutoscalerConfig
    from bigdl_tpu.serving.router import HEALTHY, Router, RouterConfig

    cmd = [sys.executable, "-m", "bigdl_tpu.serving.api_server",
           "--tiny-random", "--host", "127.0.0.1", "--port", "{port}",
           "--max-batch", "2", "--max-seq", "64"]
    router = Router(replica_cmd=cmd,
                    config=RouterConfig(replicas=n_replicas,
                                        health_sec=0.25),
                    spawn_env={"JAX_PLATFORMS": "cpu"})
    router.start()
    httpd = router.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    # ticks are driven by THIS loop, not the scaler thread:
    # deterministic decisions, and the record names the restoring tick.
    # Aggressive thresholds — one pressured poll is enough to act.
    scaler = Autoscaler(router, AutoscalerConfig(
        min_replicas=1, max_replicas=n_replicas, dwell_sec=0.0,
        up_streak=1, down_streak=10 ** 6, flip_streak=10 ** 6,
        queue_high=0.5, occupancy_high=0.2, inflight_high=1.0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, prompt_len).tolist()
               for _ in range(n_requests)]

    def healthy_count() -> int:
        return sum(1 for r in router.replicas if r.state == HEALTHY)

    def wait_healthy(n: int, timeout: float = 90.0) -> int:
        deadline = time.time() + timeout
        while time.time() < deadline and healthy_count() < n:
            time.sleep(0.1)
        return healthy_count()

    def burst() -> dict:
        results: list = []
        lock = threading.Lock()

        def one(i: int) -> None:
            body = json.dumps({"prompt": prompts[i % len(prompts)],
                               "max_tokens": new_tokens}).encode()
            req = urllib.request.Request(
                base + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    json.loads(resp.read())
                status = "ok"
            except urllib.error.HTTPError as e:
                status = "shed" if e.code == 429 else f"http_{e.code}"
            except Exception as e:
                status = type(e).__name__
            with lock:
                results.append(status)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {"n_requests": n_requests,
                "completed": results.count("ok"),
                "shed": results.count("shed"),
                "errors": sorted(s for s in results
                                 if s not in ("ok", "shed"))[:5]}

    out: dict = {"replicas": n_replicas}
    try:
        wait_healthy(n_replicas)
        out["baseline"] = burst()
        victims = [r for r in router.replicas if r.state == HEALTHY]
        with router._admin_lock:
            forced = router.retire_replica(victims[-1],
                                           reason="bench_forced_down")
        out["forced_down"] = bool(forced)
        # pressured burst in the background while the autoscaler ticks:
        # queue depth / occupancy on the survivors is the restore signal
        bg = threading.Thread(
            target=lambda: out.__setitem__("pressure", burst()))
        bg.start()
        restore_tick = None
        deadline = time.time() + 60
        while time.time() < deadline:
            d = scaler.tick()
            if d["action"] == "up":
                restore_tick = d["tick"]
                break
            time.sleep(0.1)
        bg.join()
        out["restore_tick"] = restore_tick
        out["healthy_after_restore"] = wait_healthy(n_replicas)
        out["restored"] = bool(
            out["healthy_after_restore"] >= n_replicas)
        final = burst()
        out["final"] = final
        # the gated row: zero shed at the same <=1x load post-recovery
        out["shed_total"] = final["shed"]
        out["autoscaler"] = scaler.snapshot()
    finally:
        httpd.shutdown()
        router.shutdown()
    return out


def run_prefix_share_bench(model, cfg) -> dict:
    """Shared-system-prompt lane: a wave of concurrent requests over
    one common prompt prefix through a paged-KV engine with radix
    prefix sharing on. A warmup request seeds the radix (the timed
    wave measures steady-state sharing — the state a deployed system
    prompt lives in), so every timed admission should reuse the
    prefix pages wholesale instead of re-prefilling them. Emits the
    two rows bench_diff gates: ``prefix_hit_tokens_frac`` (higher is
    better — fraction of looked-up prompt tokens served from shared
    pages) and ``page_pool_exhausted`` (lower — allocation stalls
    mean the arena is undersized for the offered load)."""
    import numpy as np

    from bigdl_tpu.observability.stats import percentile
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    # 512-token system prompt, Pallas-aligned 128-position pages
    b, prefix_len, tail_len, new_tokens = 8, 512, 8, 16
    max_seq, ps, bucket = 1024, 128, 128
    n_req = 2 * b
    eng = LLMEngine(model, EngineConfig(
        max_batch=b, max_seq=max_seq, prefix_cache_entries=0,
        prefill_bucket=bucket, prefill_chunk=bucket,
        kv_page_size=ps, prefix_sharing="on"))
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, cfg.vocab_size, prefix_len).tolist()
    prompts = [prefix + rng.integers(1, cfg.vocab_size, tail_len).tolist()
               for _ in range(n_req)]
    # warmup seeds the radix with the shared prefix AND compiles the
    # paged prefill/seed/decode executables outside the timed window
    eng.generate([prefix], SamplingParams(max_tokens=2))
    base = eng.stats_snapshot()["paged"]
    base_radix = dict(base["radix"])

    t0 = time.perf_counter()
    submit: dict = {}
    ttft: dict = {}
    finished: set = set()
    for i, p in enumerate(prompts):
        eng.add_request(f"s{i}", p, SamplingParams(max_tokens=new_tokens))
        submit[f"s{i}"] = time.perf_counter()
    generated = 0
    deadline = time.perf_counter() + 600
    while len(finished) < n_req and time.perf_counter() < deadline:
        if not eng.step():
            time.sleep(0.001)
        for rid, ts in submit.items():
            if rid in finished:
                continue
            for o in eng.get_outputs(rid):
                if o.new_token_ids and rid not in ttft:
                    ttft[rid] = time.perf_counter() - ts
                generated += len(o.new_token_ids)
                if o.finished:
                    finished.add(rid)
    wall = time.perf_counter() - t0
    snap = eng.stats_snapshot()["paged"]
    looked = snap["radix"]["lookup_tokens"] - base_radix["lookup_tokens"]
    hit = snap["radix"]["hit_tokens"] - base_radix["hit_tokens"]
    vals = sorted(ttft.values())
    return {
        "n_requests": n_req,
        "completed": len(finished),
        "prefix_len": prefix_len,
        "prompt_len": prefix_len + tail_len,
        "page_size": snap["page_size"],
        "num_pages": snap["num_pages"],
        "pages_shared_peak_hint": snap["pages_shared"],
        "generated_tokens": int(generated),
        "wall_s": round(wall, 2),
        "tokens_per_s": round(generated / max(wall, 1e-9), 1),
        "prefix_hit_tokens_frac": round(hit / max(looked, 1), 4),
        "ttft_p50_ms": (round(1000 * percentile(sorted(vals), 0.5), 1)
                        if vals else None),
        "page_pool_exhausted": int(snap["pool_exhausted_total"]
                                   - base["pool_exhausted_total"]),
        "radix_nodes": snap["radix"]["nodes"],
    }


def run_overload_bench(model, cfg, max_seq: int, prompt_len: int,
                       new_tokens: int) -> dict:
    """Open-loop overload lane: Poisson arrivals at 0.5x / 1x / 3x the
    measured closed-loop capacity, mixed QoS classes and tenants,
    against a deliberately small bounded queue. Reports goodput, shed
    rate, and per-QoS p99 TTFT per lane. bench_diff gates the <=1x
    lanes' shed_total / brownout_level_max at zero and the 3x lane's
    goodput_tokens_per_s lower-is-worse."""
    import numpy as np

    from bigdl_tpu.observability.stats import percentile
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
    from bigdl_tpu.serving.overload import RequestShed

    b = 2
    prompt_len = min(prompt_len, 64)
    new_tokens = min(new_tokens, 16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(12 * b)]

    def make_engine():
        eng = LLMEngine(model, EngineConfig(
            max_batch=b, max_seq=max_seq, prefix_cache_entries=0,
            max_queue_depth=4 * b))
        eng.generate(prompts[:b], SamplingParams(max_tokens=2))  # warmup
        return eng

    # closed-loop capacity probe: completed requests/s with every slot
    # busy — the open-loop lanes' offered rates are multiples of this
    eng = make_engine()
    n_probe = 3 * b
    t0 = time.perf_counter()
    for i in range(n_probe):
        eng.add_request(f"p{i}", prompts[i % len(prompts)],
                        SamplingParams(max_tokens=new_tokens))
    done = 0
    deadline = time.perf_counter() + 300
    while done < n_probe and time.perf_counter() < deadline:
        if not eng.step():
            time.sleep(0.001)
        for i in range(n_probe):
            done += sum(o.finished for o in eng.get_outputs(f"p{i}"))
    capacity_rps = done / max(time.perf_counter() - t0, 1e-9)

    out = {"capacity_rps": round(capacity_rps, 3),
           "max_batch": b, "prompt_len": prompt_len,
           "new_tokens": new_tokens}
    qos_cycle = ("interactive", "standard", "batch")
    for mult, tag in ((0.5, "x0.5"), (1.0, "x1"), (3.0, "x3")):
        eng = make_engine()
        rate = max(capacity_rps * mult, 1e-3)
        n_req = 6 * b
        arrivals = np.cumsum(
            np.random.default_rng(7).exponential(1.0 / rate, n_req))
        shed = 0
        submitted: dict = {}     # rid -> (qos, t_submit)
        ttft: dict = {}          # rid -> first-output latency (s)
        finished: set = set()
        generated = 0
        brownout_max = 0
        nxt = 0
        t0 = time.perf_counter()
        deadline = t0 + 300
        while (nxt < n_req or len(finished) < len(submitted)) \
                and time.perf_counter() < deadline:
            now = time.perf_counter() - t0
            while nxt < n_req and arrivals[nxt] <= now:
                rid = f"o{nxt}"
                sp = SamplingParams(
                    max_tokens=new_tokens,
                    qos=qos_cycle[nxt % 3],
                    tenant=f"tenant-{nxt % 2}")
                try:
                    eng.add_request(rid, prompts[nxt % len(prompts)], sp)
                    submitted[rid] = (sp.qos, time.perf_counter())
                except RequestShed:
                    shed += 1
                nxt += 1
            if not eng.step():
                time.sleep(0.001)
            brownout_max = max(brownout_max, eng.overload.level)
            for rid, (q, ts) in list(submitted.items()):
                if rid in finished:
                    continue
                for o in eng.get_outputs(rid):
                    if o.new_token_ids and rid not in ttft:
                        ttft[rid] = time.perf_counter() - ts
                    generated += len(o.new_token_ids)
                    if o.finished:
                        finished.add(rid)
        wall = time.perf_counter() - t0
        by_qos = {q: sorted(v for r, v in ttft.items()
                            if submitted[r][0] == q)
                  for q in qos_cycle}
        lane = {
            "offered_rps": round(rate, 3),
            "n_requests": n_req,
            "admitted": len(submitted),
            "completed": len(finished),
            "generated_tokens": int(generated),
            "wall_s": round(wall, 2),
            "ttft_p99_ms": {
                q: (round(1000 * percentile(sorted(v), 0.99), 1)
                    if v else None)
                for q, v in by_qos.items()},
        }
        # SLO lane rows: force one full burn evaluation over everything
        # the lane observed, then report what the tracker concluded.
        # bench_diff gates the <=1x rows (an alert below capacity is a
        # bug); the 3x burn rate is informational — it PROVES the
        # fast-burn alert fires under deliberate overload
        eng.slo.evaluate()
        slo_snap = eng.slo.snapshot()
        comp = {k: [c for c in (eng.slo.compliance(q, k, "fast")
                                for q in qos_cycle) if c is not None]
                for k in ("ttft", "tpot")}
        if mult <= 1.0:
            # gated: any shed or brownout below capacity is a bug
            lane["shed_total"] = shed
            lane["brownout_level_max"] = brownout_max
            lane["slo_burn_rate_max"] = slo_snap["burn_rate_max"]
            lane["slo_alerts"] = slo_snap["alerts_active"]
            lane["slo_compliance_ttft"] = (
                round(min(comp["ttft"]), 4) if comp["ttft"] else None)
            lane["slo_compliance_tpot"] = (
                round(min(comp["tpot"]), 4) if comp["tpot"] else None)
        else:
            # shedding is the POINT at 3x — gate only the goodput
            # (tokens of admitted-and-served work per second)
            lane["goodput_tokens_per_s"] = round(
                generated / max(wall, 1e-9), 1)
            lane["shed_count"] = shed
            lane["shed_rate"] = round(shed / n_req, 3)
            lane["brownout_level_peak"] = brownout_max
            lane["slo_burn_rate_overload"] = slo_snap["burn_rate_max"]
            lane["slo_alerts_overload"] = slo_snap["alerts_total"]
        out[tag] = lane
    return out


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench import _parse_kv_sweep, chip_peaks, require_tpu

    kv_sweep = _parse_kv_sweep(sys.argv[1:])
    replicas = _parse_replicas(sys.argv[1:])
    failed_lanes: "list[str]" = []

    def finish(out: dict) -> None:
        """Every exit path: run the router lane (when asked), emit the
        record, and exit nonzero listing failed lanes — one erroring
        lane records ``{"error": ...}``, the sweep continues."""
        if replicas:
            try:
                out["router_bench"] = run_router_bench(replicas)
            except Exception as e:
                failed_lanes.append("router")
                out["router_bench"] = {
                    "error": f"{type(e).__name__}: {e}"}
            # forced-scale-down recovery: its shed_total row is the
            # bench_diff gate proving the autoscaler restored zero-shed
            try:
                out["router_bench"]["autoscale"] = run_autoscale_bench(
                    max(2, min(replicas, 3)))
            except Exception as e:
                failed_lanes.append("autoscale")
                out["router_bench"]["autoscale"] = {
                    "error": f"{type(e).__name__}: {e}"}
            # rolling-restart-under-load: bench_diff gates its
            # http_5xx / recomputed_tokens_total / migrations_failed
            # rows lower-is-better (zero-loss, zero-recompute restarts)
            try:
                out["router_bench"]["restart"] = run_restart_bench(
                    max(2, min(replicas, 3)))
            except Exception as e:
                failed_lanes.append("restart")
                out["router_bench"]["restart"] = {
                    "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(out))
        if failed_lanes:
            print(f"bench_serving: {len(failed_lanes)} lane(s) failed: "
                  f"{', '.join(failed_lanes)}", file=sys.stderr)
            raise SystemExit(1)

    # read in a subprocess that has exited: the router lane spawns
    # replica processes, so which device this process takes is decided
    # only here
    device = require_tpu("bench_serving")
    import jax

    from bigdl_tpu.config import enable_compilation_cache

    enable_compilation_cache()

    import numpy as np

    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
    from bigdl_tpu.utils.testing import LLAMA2_7B, random_llama_params

    cfg = LLAMA2_7B
    batch = 8
    prompt_len, new_tokens = 128, 64
    max_seq = 512

    class _Model:
        def __init__(self):
            # merged projections + MXU int4 layout: the shipped
            # from_pretrained defaults
            from bigdl_tpu.transformers.model import _maybe_mxu_layout

            self.params = _maybe_mxu_layout(llama_mod.merge_projections(
                random_llama_params(cfg, qtype="sym_int4"), cfg))
            self.config = cfg
            self.hf_config = {"eos_token_id": None}

            class Fam:
                forward = staticmethod(llama_mod.forward)
                prefill = staticmethod(llama_mod.forward_last_token)
                new_cache = staticmethod(llama_mod.new_cache)
                forward_paged = staticmethod(llama_mod.forward_paged)
                new_paged_cache = staticmethod(llama_mod.new_paged_cache)
                SUPPORTS_SCALED_KV = llama_mod.SUPPORTS_SCALED_KV
                SUPPORTS_PAGED_KV = llama_mod.SUPPORTS_PAGED_KV

            self.family = Fam()

    model = _Model()
    from bigdl_tpu.ops.quant import QTensor

    weight_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            model.params, is_leaf=lambda x: isinstance(x, QTensor)))
    def run_wave(b: int, kv_dtype: str = "bf16") -> tuple:
        """(tokens/s, done, generated, wall_s, n_req, engine) at
        max_batch=b — the engine rides along so the caller can read
        its step-phase histograms for the critical-path report."""
        n_req = 3 * b
        eng = LLMEngine(model, EngineConfig(
            max_batch=b, max_seq=max_seq, kv_cache_dtype=kv_dtype,
            prefix_cache_entries=0))    # no reuse between identical runs
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(n_req)]
        # mixed real-world traffic: half greedy, half sampled (device)
        params_of = [
            SamplingParams(max_tokens=new_tokens) if i % 2 == 0 else
            SamplingParams(max_tokens=new_tokens, temperature=0.8,
                           top_k=32, seed=i)
            for i in range(n_req)]

        # warmup wave compiles prefill buckets, decode, the batched
        # device sampler ([B, V] shape — needs one sampled request in
        # the wave; all-greedy would take the argmax fast path and leave
        # the gumbel kernel to compile inside the timed window)
        eng.generate(prompts[:b],
                     SamplingParams(max_tokens=4, temperature=0.8,
                                    top_k=32, seed=0))
        # ...and the all-greedy argmax fast path: when a wave tail
        # drains to only greedy slots mid-window, that compile must
        # already be cached
        eng.generate(prompts[:2], SamplingParams(max_tokens=4))

        t0 = time.perf_counter()
        for i, (p, sp) in enumerate(zip(prompts, params_of)):
            eng.add_request(f"r{i}", p, sp)
        done = 0
        generated = 0
        deadline = time.perf_counter() + 1200
        while done < n_req and time.perf_counter() < deadline:
            if not eng.step():
                time.sleep(0.001)
            for i in range(n_req):
                for out in eng.get_outputs(f"r{i}"):
                    generated += len(out.new_token_ids)
                    done += out.finished
        wall = time.perf_counter() - t0
        return generated / wall, done, generated, wall, n_req, eng

    try:
        tput, done, generated, wall, n_requests, wave_eng = \
            run_wave(batch)
    except Exception as e:
        failed_lanes.append(f"serving-batch{batch}")
        return finish({
            "metric": "llama2_7b_int4_serving_tokens_per_s",
            "value": None, "unit": "tokens/s", "valid": False,
            "batch": batch, "device": device,
            "model": "llama2-7b",
            "qtype": "sym_int4",
            "error": f"{type(e).__name__}: {e}"})

    peak_tflops, peak_gbps = chip_peaks(device["kind"])
    ceiling = batch / (weight_bytes / (peak_gbps * 1e9))
    # two distinct failure modes: a deadline expiry is a real-but-slow
    # run, NOT poisoned buffers
    timed_out = done < n_requests
    poisoned = tput > ceiling / 0.8

    out = {
        "metric": "llama2_7b_int4_serving_tokens_per_s",
        "value": round(tput, 1),
        "unit": "tokens/s",
        "valid": not poisoned and not timed_out,
        "batch": batch,
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "completed": int(done),
        "generated_tokens": int(generated),
        "wall_s": round(wall, 2),
        "tokens_per_s_ceiling": round(ceiling, 1),
        "device": device,
        "model": "llama2-7b",
        "qtype": "sym_int4",
    }
    # memory report for bench_diff: wave engines keep private ledgers,
    # so register the measured config's totals in the process ledger
    from bigdl_tpu.observability.memory import default_ledger, memory_report
    from bigdl_tpu.ops.kvcache import kv_cache_nbytes

    ledger = default_ledger()
    ledger.register("weights", "bench_serving_model", int(weight_bytes),
                    qtype="sym_int4")
    ledger.register(
        "kv_cache", "bench_serving_batched",
        kv_cache_nbytes(cfg.num_hidden_layers, batch, max_seq,
                        cfg.num_key_value_heads, cfg.hd, "bf16")["total"],
        dtype="bf16", slots=batch)
    out["memory"] = memory_report(ledger)
    # critical-path decomposition (ISSUE 13): per-phase p50/p99 from the
    # engine's step-phase histograms — queue_wait/prefill are per-request,
    # dispatch/device split each decode step into host dispatch-return vs
    # blocked block_until_ready on the decode result. dispatch_overhead_ms
    # (EWMA) is the lower-is-better ratchet bench_diff gates.
    summ = wave_eng.registry.summary()
    cp: dict = {}
    for ph in ("queue_wait", "prefill", "dispatch", "device"):
        s = summ.get('bigdl_tpu_step_phase_seconds{phase="%s"}' % ph) or {}
        cp[ph] = {
            "p50_ms": round(1000.0 * s.get("p50", 0.0), 3),
            "p99_ms": round(1000.0 * s.get("p99", 0.0), 3),
            "count": int(s.get("count", 0)),
        }
    cp["dispatch_overhead_ms"] = (
        wave_eng.stats_snapshot()["dispatch_overhead_ms"])
    out["critical_path"] = cp
    # quality block (ISSUE 19): the engine's compact live-quality
    # snapshot (token NLL / entropy / margin from the measured wave)
    # plus the per-format golden NLL budget bench_diff ratchets as
    # nll_delta_vs_bf16
    from bigdl_tpu.observability.quality import golden_nll_allowance

    eng_q = wave_eng.stats_snapshot().get("quality")
    out["quality"] = {
        "qtype": wave_eng.qtype,
        "nll_delta_vs_bf16": round(
            golden_nll_allowance(wave_eng.qtype), 6),
        "live": eng_q,
    }
    # open-loop overload lane: capacity probe then Poisson arrivals at
    # 0.5x/1x/3x — bench_diff gates its shed/brownout (<=1x must stay
    # zero) and 3x goodput rows
    try:
        out["overload"] = run_overload_bench(
            model, cfg, max_seq, prompt_len, new_tokens)
    except Exception as e:
        failed_lanes.append("overload")
        out["overload"] = {"error": f"{type(e).__name__}: {e}"}
    # shared-system-prompt lane (paged KV + radix sharing): bench_diff
    # gates prefix_hit_tokens_frac higher-is-better and
    # page_pool_exhausted lower-is-better
    try:
        out["prefix_share"] = run_prefix_share_bench(model, cfg)
    except Exception as e:
        failed_lanes.append("prefix_share")
        out["prefix_share"] = {"error": f"{type(e).__name__}: {e}"}
    if kv_sweep:
        # --kv-cache-dtype rows: aggregate throughput + per-stream TPOT
        # + exact cache footprint (eval_shape, no allocation) per dtype
        from bigdl_tpu.ops.kvcache import init_cache, kv_cache_bytes

        out["kv_sweep"] = {}
        for d in kv_sweep:
            try:
                t_, d_, g_, w_, n_, _ = run_wave(batch, d)
                out["kv_sweep"][d] = {
                    "tokens_per_s": round(t_, 1),
                    "tpot_ms": round(1000.0 * batch / max(t_, 1e-9), 3),
                    "completed": int(d_),
                    "n_requests": n_,
                    "kv_cache_bytes": kv_cache_bytes(jax.eval_shape(
                        lambda d=d: init_cache(
                            cfg.num_hidden_layers, batch, max_seq,
                            cfg.num_key_value_heads, cfg.hd,
                            kv_cache_dtype=d, per_slot_pos=True))),
                }
            except Exception as e:
                # one erroring dtype lane must not cost the others'
                # already-measured rows
                failed_lanes.append(f"kv-{d}")
                out["kv_sweep"][d] = {
                    "error": f"{type(e).__name__}: {e}"}
    if poisoned:
        out["note"] = ("throughput beat the HBM ceiling — runtime did "
                       "not execute (poisoned buffers)")
    elif timed_out:
        out["note"] = (f"deadline expired with {done}/{n_requests} "
                       "requests complete — run was real but too slow")
    if poisoned or timed_out:
        failed_lanes.append(f"serving-batch{batch}")
        return finish(out)

    # the batch-8 record is already measured — print it BEFORE the
    # batch-16 wave (a fault mid-wave must not cost it); consumers
    # read the LAST line, so the combined record below supersedes this
    print(json.dumps(out), flush=True)

    # batch-16 wave: decode still reads
    # the weights once per step, so throughput should climb toward 2x —
    # KV at 16 x 512 x 0.5 MB/tok = 4 GB still fits
    try:
        t16, d16, g16, w16, n16, _ = run_wave(16)
        c16 = ceiling / batch * 16
        out["batch16"] = {
            "tokens_per_s": round(t16, 1), "completed": int(d16),
            "generated_tokens": int(g16), "wall_s": round(w16, 2),
            "n_requests": n16, "tokens_per_s_ceiling": round(c16, 1),
            "valid": bool(d16 == n16 and t16 <= c16 / 0.8),
        }
    except Exception as e:
        # the batch-8 record above is already on disk; keep it
        failed_lanes.append("serving-batch16")
        out["batch16"] = {"error": f"{type(e).__name__}: {e}"}
    finish(out)


if __name__ == "__main__":
    main()
