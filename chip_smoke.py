#!/usr/bin/env python3
"""The quickest proof that bigdl-tpu still starts on the chip.

    python3 chip_smoke.py              # one chip: build, serve, serve-paged-int8, qlora
    python3 chip_smoke.py --chips 4    # four chips: explicit tensor parallelism, nothing else
    python3 chip_smoke.py --tiny       # same control flow, toy widths, on the CPU (rehearsal)

Drives the system's main paths once, through the entry points a user
calls, at the published width and full depth of Mistral-7B-v0.1
(sym_int4 weights, random from --seed): the model is built and saved by
one process, served by ``python -m bigdl_tpu.serving.api_server`` (slab
bf16 KV, then paged + int8 KV + prefix sharing) and finetuned by a
QLoRA process. Every phase is a child process that has exited before
the next starts — a chip belongs to one process at a time — and this
parent never imports jax or bigdl_tpu. One JSON object per phase goes
to stdout with its checks and wall time (smoke timings, not metrics);
the LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reported it in the build child. Any failed
check, any child that exits non-zero, any phase that cannot run, or a
device that is not a TPU gives ``"ok": false`` and exit code 1.
``--tiny`` is the only way to make the script accept another device,
and then ``ok`` speaks for the control flow only.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
GB = 1e9


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def child_env(args, devices: int = 1) -> dict:
    """Children run with the repo root importable (the package is not
    installed) and with JAX's own compiler log on, so the parent can
    count the persistent-cache hits and misses JAX reports."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    if args.tiny:
        env["JAX_PLATFORMS"] = "cpu"
        if devices > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={devices}")
    return env


def cache_counts(log_path: str) -> dict:
    """Persistent compilation cache hits / misses as JAX logged them."""
    hits = misses = 0
    with open(log_path, errors="replace") as f:
        for line in f:
            hits += "Persistent compilation cache hit" in line
            misses += "PERSISTENT COMPILATION CACHE MISS" in line
    return {"hits": hits, "misses": misses}


def last_json(text: str):
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def run_child(args, phase: str, extra: list, devices: int = 1) -> dict:
    """One ``python -m bigdl_tpu.smoke <phase>`` process, run to its end."""
    log = os.path.join(LOG_DIR, f"{phase}.log")
    cmd = [sys.executable, "-u", "-m", "bigdl_tpu.smoke", phase,
           "--seed", str(args.seed)] + extra + (
               ["--tiny"] if args.tiny else [])
    t0 = time.time()
    with open(log, "w") as err:
        proc = subprocess.run(cmd, cwd=ROOT, env=child_env(args, devices),
                              stdout=subprocess.PIPE, stderr=err, text=True)
    rec = last_json(proc.stdout) or {"phase": phase, "checks": {}}
    rec["exit_code"] = proc.returncode
    rec["ok"] = bool(rec.get("ok")) and proc.returncode == 0
    rec["compile_cache"] = cache_counts(log)
    rec["phase_wall_s"] = round(time.time() - t0, 2)
    if not rec["ok"]:
        with open(log, errors="replace") as f:
            rec["log_tail"] = f.read()[-1500:]
    emit(rec)
    return rec


# ----------------------------------------------------------------- serving

def http_open(port: int, path: str, body=None, timeout: float = 900.0):
    return urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}), timeout=timeout)


def http(port: int, path: str, body=None, timeout: float = 900.0) -> str:
    with http_open(port, path, body, timeout) as resp:
        return resp.read().decode()


def completion(port: int, req: dict) -> dict:
    """POST one /v1/completions; returns what the checks need. Without
    a tokenizer the server's text is the space-joined token ids."""
    body = {"prompt": req["prompt"], "max_tokens": req["max_tokens"],
            "ignore_eos": True, **req["sampling"]}
    t0 = time.time()
    try:
        if not req.get("stream"):
            doc = json.loads(http(port, "/v1/completions", body))
            choice = doc["choices"][0]
            out = {"text": choice["text"],
                   "finish_reason": choice["finish_reason"],
                   "completion_tokens": doc["usage"]["completion_tokens"]}
        else:
            text, done = "", False
            with http_open(port, "/v1/completions",
                           dict(body, stream=True)) as resp:
                for raw in resp:
                    line = raw.decode().strip()
                    if line == "data: [DONE]":
                        done = True
                    elif line.startswith("data: "):
                        text += json.loads(line[6:])["choices"][0]["text"]
            # SSE chunks carry no finish_reason; [DONE] ends the stream
            out = {"text": text, "finish_reason": "length" if done else None,
                   "completion_tokens": len(text.split())}
    except Exception as e:  # noqa: BLE001 — reported, and fails the check
        out = {"error": f"{type(e).__name__}: {e}"}
    out["wall_s"] = round(time.time() - t0, 2)
    return out


def parse_metrics(text: str) -> list:
    """Prometheus text -> [(name, {label: value}, float)]."""
    rows = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        for part in rest.rstrip("}").split('",'):
            if "=" in part:
                k, _, v = part.partition("=")
                labels[k.strip()] = v.strip().strip('"')
        try:
            rows.append((name, labels, float(val)))
        except ValueError:
            pass
    return rows


def metric_sum(rows, name: str, **labels) -> float:
    return sum(v for n, lab, v in rows if n == name
               and all(lab.get(k) == want for k, want in labels.items()))


def serve_requests(args, paged: bool) -> list:
    """The seeded request set of a serve phase: token-id prompts, half
    greedy and half sampled, one of them streamed."""
    rng = random.Random(args.seed + (1 if paged else 0))
    vocab = 256 if args.tiny else 32000
    lo, hi = (16, 128) if args.tiny else (128, 1024)
    new_lo, new_hi = (4, 8) if args.tiny else (32, 64)

    def toks(n):
        return [rng.randrange(3, vocab) for _ in range(n)]

    if paged:
        # 4 requests sharing a 256-token prefix (two 128-position
        # pages), private tails short of the next prefill bucket
        prefix = toks(32 if args.tiny else 256)
        prompts = [prefix + toks(rng.randrange(*((8, 30) if args.tiny
                                                 else (64, 250))))
                   for _ in range(4)]
    else:
        prompts = [toks(rng.randrange(lo, hi + 1)) for _ in range(8)]
    reqs = []
    for i, p in enumerate(prompts):
        greedy = i % 2 == 0
        reqs.append({
            "prompt": p, "max_tokens": rng.randrange(new_lo, new_hi + 1),
            "sampling": {} if greedy else {
                "temperature": 0.8, "top_k": 32, "seed": i},
            "stream": i == 3})
    return reqs


def phase_serve(args, model_dir: str, paged: bool) -> dict:
    phase = "serve_paged_int8" if paged else "serve"
    log = os.path.join(LOG_DIR, f"{phase}.log")
    max_seq = 256 if args.tiny else 2048
    # a page's positions are whole lane tiles (128) of the paged Pallas
    # kernel's scores: the dispatch rule sends smaller pages through the
    # XLA gather, so a 16-position page would leave the block-table
    # kernel unrun
    page = 16 if args.tiny else 128
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cmd = [sys.executable, "-u", "-m", "bigdl_tpu.serving.api_server",
           "--model", model_dir, "--max-batch", "8",
           "--max-seq", str(max_seq), "--port", str(port)]
    env = child_env(args)
    if paged:
        cmd += ["--kv-page-size", str(page), "--prefix-sharing", "on"]
        env["BIGDL_TPU_KV_CACHE_DTYPE"] = "int8"
    rec: dict = {"phase": phase, "checks": {}}
    checks = rec["checks"]
    t0 = time.time()
    with open(log, "w") as err:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stderr=err,
                                stdout=subprocess.DEVNULL)
    try:
        healthy = False
        while proc.poll() is None and time.time() - t0 < 600:
            try:
                healthy = "ok" in http(port, "/health", timeout=5)
                break
            except OSError:
                time.sleep(0.5)
        checks["server_healthy"] = healthy
        rec["time_to_health_s"] = round(time.time() - t0, 2)
        if healthy:
            drive_server(args, port, paged, rec)
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        checks["sigterm_drains_and_exits_0"] = proc.returncode == 0
        rec["exit_code"] = proc.returncode
    rec["compile_cache"] = cache_counts(log)
    rec["phase_wall_s"] = round(time.time() - t0, 2)
    rec["ok"] = bool(checks) and all(checks.values())
    if not rec["ok"]:
        with open(log, errors="replace") as f:
            rec["log_tail"] = f.read()[-1500:]
    emit(rec)
    return rec


def drive_server(args, port: int, paged: bool, rec: dict) -> None:
    checks = rec["checks"]
    reqs = serve_requests(args, paged)
    results: list = [None] * len(reqs)

    def worker(i):
        results[i] = completion(port, reqs[i])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(reqs))]
    # request 0 (greedy) goes in a moment ahead of the rest, so the
    # all-greedy decode executable is compiled in this wave and the
    # repeat below has nothing left to compile
    threads[0].start()
    time.sleep(0.5)
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join()
    before = parse_metrics(http(port, "/metrics"))
    repeat = completion(port, reqs[0])
    metrics = parse_metrics(http(port, "/metrics"))
    stats = json.loads(http(port, "/v1/stats"))
    memory = json.loads(http(port, "/v1/memory"))

    rec["requests_smoke_timing"] = [
        {"prompt_tokens": len(q["prompt"]), "max_tokens": q["max_tokens"],
         "sampled": bool(q["sampling"]), "stream": q["stream"],
         "wall_s": r["wall_s"], **({"error": r["error"]}
                                   if "error" in r else {})}
        for q, r in zip(reqs, results)]
    rec["repeat_wall_s_smoke_timing"] = repeat["wall_s"]
    checks["every_response_has_requested_tokens"] = all(
        r.get("completion_tokens") == q["max_tokens"]
        and len(r.get("text", "").split()) == q["max_tokens"]
        for q, r in zip(reqs, results))
    checks["every_finish_reason_is_length"] = all(
        r.get("finish_reason") == "length" for r in results)
    checks["greedy_repeat_byte_identical"] = (
        "text" in repeat and repeat["text"] == results[0].get("text"))

    def compiles(rows):
        return metric_sum(rows, "bigdl_tpu_jit_compiles_total")

    checks["repeat_adds_no_compile"] = compiles(metrics) == compiles(before)
    rec["jit_compiles_total"] = compiles(metrics)
    checks["no_request_quarantined"] = metric_sum(
        metrics, "bigdl_tpu_requests_quarantined_total") == 0
    checks["no_step_retried"] = metric_sum(
        metrics, "bigdl_tpu_step_retries_total") == 0 \
        and stats["robustness"]["consecutive_failures"] == 0

    table = stats["compile_table"]
    decode_fn = "engine_decode_paged" if paged else "engine_decode_resident"
    checks[f"{decode_fn}_compiled"] = \
        table.get(decode_fn, {}).get("compiles", 0) > 0
    rec["compile_table_s_smoke_timing"] = {
        k: v["total_s"] for k, v in table.items() if v["compiles"]}

    dev = memory.get("device", {})
    weights = memory["static"]["by_kind"].get("weights", 0)
    rec["memory"] = {"weights_bytes": weights, "device": dev,
                     "kv_cache_dtype": memory["engine"]["kv_cache_dtype"]}
    checks["kv_cache_dtype_as_configured"] = \
        memory["engine"]["kv_cache_dtype"] == ("int8" if paged else "bf16")
    if paged:
        radix = stats["paged"]["radix"]
        rec["paged"] = {k: stats["paged"][k] for k in (
            "page_size", "num_pages", "pool_exhausted_total")}
        rec["paged"]["radix"] = radix
        checks["radix_hits_at_least_3"] = radix["hits"] >= 3
        checks["page_pool_never_exhausted"] = \
            stats["paged"]["pool_exhausted_total"] == 0
    if args.tiny:
        return
    # what only a TPU can show: the kernels ran, nothing fell back
    probes = {(lab.get("kernel"), lab.get("outcome")): v
              for n, lab, v in metrics
              if n == "bigdl_tpu_kernel_probe_total"}
    rec["kernel_probes"] = {f"{k}:{o}": v for (k, o), v in probes.items()}
    checks["no_kernel_probe_fell_back"] = all(
        v == 0 for (_, o), v in probes.items() if o == "fallback")
    checks["gemv_kernel_compiled"] = probes.get(
        ("gemv_mxu", "compiled"), 0) > 0
    checks["decode_attention_kernel_compiled"] = probes.get(
        ("paged_decode_attention" if paged else "decode_attention",
         "compiled"), 0) > 0
    checks["live_bytes_within_16GB"] = \
        0 < dev.get("bytes_in_use", 0) <= min(16 * GB,
                                              dev.get("bytes_limit", 0))
    checks["weights_about_4GB"] = 3.5 * GB <= weights <= 5.0 * GB
    if paged:
        checks["int8_kv_dequant_fused"] = metric_sum(
            metrics, "bigdl_tpu_kv_dequant_path_total", path="fused") > 0
        checks["int8_kv_dequant_never_xla"] = metric_sum(
            metrics, "bigdl_tpu_kv_dequant_path_total", path="xla") == 0


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run ONLY the four-chip tensor-parallel "
                         "path and what it is compared with")
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths on JAX_PLATFORMS=cpu: a rehearsal "
                         "of the control flow, never a chip result")
    args = ap.parse_args(argv)
    os.makedirs(LOG_DIR, exist_ok=True)
    t0 = time.time()
    if not args.tiny:
        emit({"note": "Mistral-7B-v0.1 publishes sliding_window 4096; "
                      "the smoke serves max_seq 2048, where that window "
                      "masks nothing and dispatch drops it "
                      "(ops/attention._live_window). The kernels "
                      "implement no window beyond it (ROADMAP R3)."})
    device = None
    phases = []
    if args.chips == 4:
        rec = run_child(args, "tp", [], devices=4)
        device = rec.get("device")
        phases.append(rec)
    else:
        work = tempfile.mkdtemp(prefix="chip_smoke_")
        model_dir = os.path.join(work, "mistral7b_sym_int4")
        try:
            rec = run_child(args, "build", ["--out", model_dir])
            device = rec.get("device")
            phases.append(rec)
            if rec["ok"]:
                phases.append(phase_serve(args, model_dir, paged=False))
                phases.append(phase_serve(args, model_dir, paged=True))
                phases.append(run_child(args, "qlora",
                                        ["--out", model_dir]))
        finally:
            shutil.rmtree(work, ignore_errors=True)
    want = 4 if args.chips == 4 else 1
    on_chip = bool(device) and device.get("platform") == "tpu" \
        and device.get("count", 0) >= want
    ok = all(p["ok"] for p in phases) \
        and len(phases) == (1 if args.chips == 4 else 4) \
        and (on_chip or args.tiny)
    emit({"summary": {p["phase"]: p["ok"] for p in phases},
          "total_wall_s": round(time.time() - t0, 2)})
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
