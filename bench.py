"""Headline benchmark: Llama2-7B INT4, bs=1 decode latency on one TPU chip.

Mirrors the reference's BenchmarkWrapper metric (BASELINE.md: first-token
latency + mean next-token latency, 1024-128-style run). Weights are random
(quantized on device) — latency does not depend on weight values. Decode is
timed as a jitted K-step lax.scan so host overhead never pollutes the
per-token number.

The run A/Bs the kernel dispatch configurations (Pallas decode GEMV /
generic Pallas tiles / XLA matmul x Pallas / XLA attention) and reports
the shipped default as the headline, with every configuration's numbers
in the JSON extras.

Each configuration runs in its OWN subprocess, one after the other: a
chip belongs to one process at a time, so the parent never touches JAX,
and a kernel fault in one lane cannot poison the next lane's runtime.
Physics floors (HBM roofline for decode, MXU peak for prefill) reject
timings no hardware could produce, recording them as `invalid` instead
of as results.

There is no measurement without a chip: with no TPU the script prints
`{"ok": false, ...}` and exits non-zero, and a lane that produced no
number fails the run. Nothing is read from, or replayed out of, an
earlier run.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
`vs_baseline` is speedup vs 30 ms/token, our documented stand-in for the
reference's Intel Max 1550 Llama2-7B INT4 decode latency (the reference
publishes no absolute tables; see BASELINE.md).
"""

from __future__ import annotations

import functools
import json
import os
import re
import subprocess
import sys
import time


def _last_tb_frame(stderr: str) -> str:
    """Last real traceback frame in a lane's stderr. Lanes run with
    JAX_TRACEBACK_FILTERING=off, so this names the actual crash site
    instead of jax's re-raise shim — the one line that makes an
    erroring A/B lane diagnosable from the bench JSON alone."""
    frames = re.findall(r'File "[^"]*", line \d+, in \S+', stderr or "")
    return frames[-1] if frames else ""


def _exception_head(stderr: str) -> str:
    """The terminal ``SomeError: message`` line in a lane's stderr: the
    exception head is what differs between failure modes (stderr TAILS
    are mostly runtime-shutdown noise), so it goes into the lane's JSON
    error string alongside the crash frame and the tail."""
    heads = [ln for ln in (stderr or "").splitlines()
             if re.match(r"^[A-Za-z_.]+(Error|Exception|Fault|Exit)\b[:(]",
                         ln)]
    return heads[-1][:200] if heads else ""


def require_tpu(who: str = "bench") -> dict:
    """The device this machine offers, read in a SUBPROCESS that has
    exited before any lane starts (a chip belongs to one process at a
    time, and the parent stays off JAX). No TPU -> one ``ok: false``
    JSON line and a non-zero exit: a measurement path that finds no
    chip fails, it does not fall back to the CPU."""
    code = ("import json, jax; d = jax.devices();"
            "print(json.dumps({'platform': d[0].platform,"
            "'kind': d[0].device_kind, 'count': len(d)}))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    lines = r.stdout.strip().splitlines()
    device = json.loads(lines[-1]) if r.returncode == 0 and lines else None
    if device is None or device["platform"] != "tpu":
        print(json.dumps({
            "ok": False, "device": device,
            "error": f"{who}: no TPU on this machine"
                     + ("" if device else f": {r.stderr[-300:]}")}))
        raise SystemExit(1)
    return device


BASELINE_NEXT_TOKEN_MS = 30.0
PROMPT_LEN = 1024
DECODE_STEPS = 64
MAX_SEQ = 2048
CONFIG_TIMEOUT_S = int(os.environ.get("BENCH_CONFIG_TIMEOUT_S", "900"))

# (label, flag overrides) — the dispatch configurations to A/B on TPU.
# "pallas+gemv" is the shipped default: Pallas kernels up to
# matmul_pallas_max_m rows (decode and prefill chunks), the XLA matmul
# above. "pallas-all-m" forces the dequant kernel at every M.
AB_CONFIGS = [
    ("pallas+gemv", dict(matmul_backend="auto", attention_backend="auto",
                         matmul_gemv="auto")),
    ("gemv-mxuflat", dict(matmul_backend="auto", attention_backend="auto",
                          matmul_gemv="mxuflat")),
    ("gemv-mxu8", dict(matmul_backend="auto", attention_backend="auto",
                       matmul_gemv="mxu8")),
    ("no-mxu-layout", dict(matmul_backend="auto", attention_backend="auto",
                           matmul_gemv="auto", mxu_layout="off")),
    ("gemv-fold", dict(matmul_backend="auto", attention_backend="auto",
                       matmul_gemv="fold", mxu_layout="off")),
    ("xla-matmul", dict(matmul_backend="xla", attention_backend="auto",
                        matmul_gemv="off")),
    ("no-merge", dict(matmul_backend="auto", attention_backend="auto",
                      matmul_gemv="auto", _merged=False)),
    ("xla-attn", dict(matmul_backend="auto", attention_backend="xla",
                      matmul_gemv="auto")),
    ("pallas", dict(matmul_backend="auto", attention_backend="auto",
                    matmul_gemv="off")),
    ("pallas-all-m", dict(matmul_backend="auto", attention_backend="auto",
                          matmul_gemv="auto",
                          matmul_pallas_max_m=1 << 30)),
    ("xla", dict(matmul_backend="xla", attention_backend="xla",
                 matmul_gemv="off")),
    # experiments beyond the dispatch matrix (keys starting with "_" are
    # bench_config parameters, not flags). int8: the in-kernel int4
    # dequant is VPU-bound (see matmul_pallas_max_m docstring) — int8's
    # cheaper unpack may decode FASTER despite 2x the HBM bytes. fp8-kv:
    # same int4 model with the e5m2 KV cache (halves KV traffic and
    # exercises the fp8 decode-attention kernel on chip).
    ("int8-weights", dict(matmul_backend="auto", attention_backend="auto",
                          matmul_gemv="auto", _qtype="sym_int8")),
    ("fp8-kv", dict(matmul_backend="auto", attention_backend="auto",
                    matmul_gemv="auto", _kv_cache_dtype="fp8_e5m2")),
]

# `--kv-cache-dtype a,b,...` sweep rows (not part of the default A/B
# matrix): each dtype runs the shipped dispatch flags with only the KV
# storage dtype varied, so the per-dtype TPOT/kv_cache_bytes deltas are
# attributable to the cache alone
KV_SWEEP_FLAGS = dict(matmul_backend="auto", attention_backend="auto",
                      matmul_gemv="auto")


def bench_config(qtype: str = "sym_int4", kv_quantized: bool = False,
                 merged: bool = True,
                 kv_cache_dtype: "str | None" = None) -> dict:
    """Time prefill + decode under the AMBIENT flags; returns raw numbers.

    Refuses to run off-TPU. The final token is transferred to host and
    its value recorded — a poisoned device buffer (crashed runtime)
    either raises here or yields timings below the physics floors the
    parent checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from bigdl_tpu.config import enable_compilation_cache
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.ops.kvcache import kv_cache_bytes, resolve_kv_cache_dtype
    from bigdl_tpu.utils.testing import (LLAMA2_7B, TINY_LLAMA,
                                         random_llama_params)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"bench lane needs a TPU, found {dev.platform}")
    kv_dtype = resolve_kv_cache_dtype(
        kv_cache_dtype if kv_cache_dtype is not None else kv_quantized)

    # compiled 7B programs are shared by the lanes' subprocesses
    enable_compilation_cache()

    def phase(msg: str) -> None:
        # progress breadcrumbs on stderr: a config timeout must say WHERE
        # it wedged (compile vs first execution vs steady-state timing)
        print(f"bench-phase[{time.strftime('%H:%M:%S')}]: {msg}",
              file=sys.stderr, flush=True)

    cfg = LLAMA2_7B
    max_seq, prompt_len, steps = MAX_SEQ, PROMPT_LEN, DECODE_STEPS

    from bigdl_tpu.ops.quant import prepack_tree

    if os.environ.get("BENCH_CANARY", "1") != "0":
        # tiny-geometry run under the SAME dispatch flags: if the 7B run
        # wedges but this passes, the fault is geometry-dependent
        phase("canary: tiny-geometry forward under ambient flags")
        tp = random_llama_params(TINY_LLAMA, qtype=qtype)
        if merged:
            tp = llama_mod.merge_projections(tp, TINY_LLAMA)
        tp, _ = prepack_tree(tp)
        tcache = llama_mod.new_cache(TINY_LLAMA, 1, 64,
                                     quantized=kv_dtype)
        tlg, tcache = jax.jit(llama_mod.forward, static_argnums=1)(
            tp, TINY_LLAMA, jnp.ones((1, 8), jnp.int32), tcache)
        np.asarray(tlg)
        phase("canary ok")
        del tp, tcache, tlg

    phase(f"generating {qtype} params")
    params = random_llama_params(cfg, qtype=qtype)
    if merged:
        # merged QKV + gate/up — the shipped from_pretrained default
        params = llama_mod.merge_projections(params, cfg)
    # the shipped from_pretrained load-time prepack (int4-dtype MXU
    # weight re-layout) — ONE implementation so bench measures exactly
    # what the loader does; the report rides along in the bench JSON
    t_pack = time.perf_counter()
    params, prepack_report = prepack_tree(params)
    jax.block_until_ready(params)
    prepack_report["prepack_ms"] = round(
        (time.perf_counter() - t_pack) * 1e3, 1)
    phase("params ready on device")
    tokens = jnp.ones((1, prompt_len), jnp.int32)

    prefill = jax.jit(llama_mod.forward_last_token, static_argnums=1,
                      donate_argnums=3)

    def make_decode(n_steps: int):
        @functools.partial(jax.jit, donate_argnums=(2,))
        def decode_steps(params, tok, cache):
            def step(carry, _):
                tok, cache = carry
                logits, cache = llama_mod.forward(params, cfg,
                                                  tok[:, None], cache)
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(
                    jnp.int32)
                return (nxt, cache), None
            (tok, cache), _ = lax.scan(step, (tok, cache), None,
                                       length=n_steps)
            return tok, cache
        return decode_steps

    # Decode latency is the DIFFERENCE of two in-jit loop counts, each
    # ended with a forced host readback. Differencing cancels every
    # fixed per-call cost (dispatch, readback) and leaves pure per-token
    # time; it also zeroes out when a crashed runtime returns poisoned
    # buffers instantly, which the parent's physics floor then rejects.
    short, long_ = max(steps // 4, 1), steps
    dec_short, dec_long = make_decode(short), make_decode(long_)

    def run(decode_fn, tag=None):
        cache = llama_mod.new_cache(cfg, 1, max_seq,
                                    quantized=kv_dtype)
        t0 = time.perf_counter()
        logits, cache = prefill(params, cfg, tokens, cache)
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        np.asarray(tok)                          # forced readback
        first_ms = (time.perf_counter() - t0) * 1e3
        if tag:
            phase(f"{tag}: prefill done ({first_ms:.0f}ms)")
        t1 = time.perf_counter()
        tok, cache = decode_fn(params, tok, cache)
        final = int(np.asarray(tok)[0])          # forced readback
        dec_ms = (time.perf_counter() - t1) * 1e3
        if tag:
            phase(f"{tag}: decode done ({dec_ms:.0f}ms)")
        return first_ms, dec_ms, final

    run(dec_short, tag="warmup-short")   # warmup: compile prefill + short
    run(dec_long, tag="warmup-long")     # warmup: compile long
    firsts, shorts, longs, final = [], [], [], 0
    for it in range(3):
        f, dm, final = run(dec_short)
        firsts.append(f)
        shorts.append(dm)
        f, dm, final = run(dec_long)
        firsts.append(f)
        longs.append(dm)
        phase(f"timing iter {it + 1}/3 done")
    next_ms = (min(longs) - min(shorts)) / (long_ - short)
    first_ms = min(firsts)
    from bigdl_tpu.ops.quant import QTensor

    # QTensor.nbytes owns the int4-packing byte accounting; plain
    # arrays (norms, rope tables) report their own nbytes
    weight_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor)))
    # observability snapshot rides along in the JSON: TTFT/TPOT sample
    # distributions from this run's timing iters plus whatever the
    # default registry accumulated (kernel probe outcomes, speculative
    # acceptance when a spec bench ran in-process)
    from bigdl_tpu.observability.metrics import (MetricsRegistry,
                                                 default_registry)

    obs = MetricsRegistry()
    ttft_h = obs.histogram("bigdl_tpu_ttft_seconds",
                           "Prefill + first token wall time.")
    for f in firsts:
        ttft_h.observe(f / 1e3)
    obs.histogram("bigdl_tpu_tpot_seconds",
                  "Differenced per-token decode time.").observe(
        next_ms / 1e3)
    obs_summary = obs.summary()
    obs_summary.update(default_registry().summary())
    from bigdl_tpu.observability.compile_watch import compile_table
    from bigdl_tpu.observability.memory import default_ledger, memory_report

    kv_bytes = kv_cache_bytes(jax.eval_shape(
        lambda: llama_mod.new_cache(cfg, 1, max_seq,
                                    quantized=kv_dtype)))
    ledger = default_ledger()
    ledger.register("weights", "bench_model", int(weight_bytes),
                    qtype=qtype)
    ledger.register("kv_cache", "bench_cache", kv_bytes["total"],
                    dtype=kv_dtype)

    # quality block (quantization-error observability): the per-format
    # golden NLL budget from ACCURACY.md (shrink-only ratcheted by
    # tools/bench_diff.py as nll_delta_vs_bf16) plus one measured
    # weight-error sample — a fixed-seed matrix quantized at the bench
    # qtype and scored by the same weight_error_stats the load-time
    # attribution uses, so a kernel-level encode regression moves a
    # bench number even without a checkpoint to convert
    from bigdl_tpu.observability.quality import (golden_nll_allowance,
                                                 weight_error_stats)
    from bigdl_tpu.ops.quant import (FLOAT_QTYPES, dequantize_linear,
                                     quantize_linear)

    q_sample = None
    if qtype not in FLOAT_QTYPES:
        try:
            w_ref = np.random.default_rng(0).standard_normal(
                (256, 256)).astype(np.float32)
            qt = quantize_linear(jnp.asarray(w_ref), qtype)
            q_sample = weight_error_stats(
                w_ref, np.asarray(dequantize_linear(qt, jnp.float32)))
        except Exception:
            q_sample = None     # telemetry, never fails the bench
    quality_block = {
        "qtype": qtype,
        "nll_delta_vs_bf16": round(golden_nll_allowance(qtype), 6),
        "weight_error_sample": q_sample,
    }

    return {
        "quality": quality_block,
        "observability": obs_summary,
        # static ledger totals + live device stats (TPU runs) + peak
        # jit scratch — tools/bench_diff.py compares the headline
        # scalars under --max-hbm-regress-pct
        "memory": memory_report(ledger),
        # per-executable compile counts/times for this process — a bench
        # row whose compile table grew between runs recompiled something
        "jit_compile_table": compile_table(),
        # load-time weight prepack report (ISSUE 14c): mode, QTensor
        # counts, bytes re-laid-out, and the one-time transform cost —
        # tools/bench_diff.py treats the block as informational
        "prepack": prepack_report,
        "first_token_ms": round(first_ms, 3),
        "next_token_ms": round(next_ms, 3),
        "final_token": final,
        "weight_bytes": int(weight_bytes),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "prompt_len": prompt_len,
        "decode_steps": steps,
        "qtype": qtype,
        "kv_cache_dtype": kv_dtype,
        "kv_quantized": kv_dtype != "bf16",
        # logical cache footprint (eval_shape: no second allocation);
        # int4 counted at two codes per byte
        "kv_cache_bytes": kv_bytes,
    }


# single-sourced roofline math (bigdl_tpu/observability/roofline.py):
# the same functions drive the physics floors below, the efficiency
# block, bench_qlora/bench_serving/bench_speculative AND the serving
# engine's live bigdl_tpu_roofline_util gauges —
# tests/test_perf_observability.py asserts bench output is
# value-identical to the model on fixed fixture numbers, so the
# offline bench and the live gauges cannot silently drift
from bigdl_tpu.observability.roofline import (  # noqa: E402
    chip_peaks, model_flops_per_token)
from bigdl_tpu.observability import roofline as _roofline  # noqa: E402


def _floors(cfg, weight_bytes: int, prompt_len: int,
            device_kind: "str | None" = None) -> tuple:
    """(decode_floor_ms, prefill_floor_ms): timings below these are
    physically impossible on one chip and mean the runtime did not
    actually execute. Peaks are those of `device_kind`
    (roofline.CHIP_PEAKS); an unknown kind raises."""
    peak_tflops, peak_gbps = chip_peaks(device_kind)
    # decode reads at least the packed weights once per token
    decode_floor = weight_bytes / (peak_gbps * 1e9) * 1e3 * 0.8
    prefill_floor = (prompt_len * model_flops_per_token(cfg)) / (
        peak_tflops * 1e12) * 1e3 * 0.5
    return decode_floor, prefill_floor


def _one_config(label: str) -> None:
    """Subprocess entry: run ONE dispatch configuration, print JSON.

    `kv-<dtype>` labels are the --kv-cache-dtype sweep rows: shipped
    dispatch flags, only the KV storage dtype varied. A crash exits
    non-zero with its traceback: a lane that cannot run is a failed
    lane."""
    cfgs = dict(AB_CONFIGS)
    if label in cfgs:
        overrides = dict(cfgs[label])
    elif label.startswith("kv-"):
        overrides = dict(KV_SWEEP_FLAGS, _kv_cache_dtype=label[3:])
    else:
        raise KeyError(label)
    qtype = overrides.pop("_qtype", "sym_int4")
    kv_quantized = overrides.pop("_kv_quantized", False)
    kv_cache_dtype = overrides.pop("_kv_cache_dtype", None)
    merged = overrides.pop("_merged", True)
    from bigdl_tpu.config import set_flags

    set_flags(**overrides)
    print(json.dumps(bench_config(
        qtype=qtype, kv_quantized=kv_quantized, merged=merged,
        kv_cache_dtype=kv_cache_dtype)))


def main(kv_sweep: "list[str] | None" = None) -> None:
    device = require_tpu("bench")

    from bigdl_tpu.utils.testing import LLAMA2_7B

    record = {
        "metric": "llama2_7b_int4_next_token_latency",
        "value": None,
        "unit": "ms",
        "vs_baseline": 0.0,
        "valid": False,
        "prompt_len": PROMPT_LEN,
        "decode_steps": DECODE_STEPS,
        "device": device,
        "model": "llama2-7b",
        "qtype": "sym_int4",
        "best_config": None,
        "ab": {},
    }

    ab_results = {}
    schedule = ([f"kv-{d}" for d in kv_sweep] if kv_sweep
                else [label for label, _ in AB_CONFIGS])
    for label in schedule:
        t0 = time.time()
        try:
            # unfiltered tracebacks: the child's crash frames must name
            # the real site, not jax's traceback-filtering shim
            proc = subprocess.run(
                [sys.executable, "-u", os.path.abspath(__file__),
                 "--config", label],
                capture_output=True, text=True, timeout=CONFIG_TIMEOUT_S,
                env={**os.environ, "JAX_TRACEBACK_FILTERING": "off"})
        except subprocess.TimeoutExpired as te:
            child_err = te.stderr or ""
            if isinstance(child_err, bytes):
                child_err = child_err.decode("utf-8", "replace")
            sys.stderr.write(child_err[-2000:])
            crumbs = [ln for ln in child_err.splitlines()
                      if "bench-phase" in ln]
            ab_results[label] = {
                "error": f"timeout {CONFIG_TIMEOUT_S}s after: "
                         + (crumbs[-1][-120:] if crumbs
                            else "no phase reached")}
            print(f"bench[{label}]: TIMEOUT", file=sys.stderr)
            continue
        sys.stderr.write(proc.stderr[-2000:])
        lines = [ln for ln in proc.stdout.strip().splitlines()
                 if ln.startswith("{")]
        if proc.returncode != 0 or not lines:
            head = _exception_head(proc.stderr)
            frame = _last_tb_frame(proc.stderr)
            ab_results[label] = {
                "error": f"no output (rc={proc.returncode}); "
                         + (f"{head}; " if head else "")
                         + (f"crashed at: {frame}; " if frame else "")
                         + f"stderr tail: {proc.stderr[-300:]}"}
            print(f"bench[{label}]: FAILED", file=sys.stderr)
            continue
        raw = json.loads(lines[-1])
        dfloor, pfloor = _floors(LLAMA2_7B, raw["weight_bytes"],
                                 raw["prompt_len"], device["kind"])
        entry = {k: raw.get(k) for k in (
            "first_token_ms", "next_token_ms", "final_token",
            "weight_bytes", "qtype", "kv_cache_dtype", "kv_cache_bytes",
            "kv_quantized", "prepack", "observability", "device")}
        if raw["next_token_ms"] < dfloor or \
                raw["first_token_ms"] < pfloor:
            entry["invalid"] = (
                f"timings beat the physics floors "
                f"(decode>{dfloor:.2f}ms, prefill>{pfloor:.1f}ms) — "
                f"runtime did not execute (poisoned buffers)")
        ab_results[label] = entry
        print(f"bench[{label}]: first {raw['first_token_ms']:.1f}ms "
              f"next {raw['next_token_ms']:.2f}ms "
              f"({'INVALID' if 'invalid' in entry else 'ok'}, "
              f"{time.time() - t0:.0f}s)", file=sys.stderr)

    # headline candidates: valid AND the shipped default model config —
    # int4 weights, bf16 KV (experiment configs like int8-weights and
    # fp8-kv stay in `ab` as evidence)
    ok = {k: v for k, v in ab_results.items()
          if "next_token_ms" in v and "invalid" not in v
          and v.get("qtype") == "sym_int4"
          and not v.get("kv_quantized")}
    record["ab"] = ab_results
    if kv_sweep:
        record["kv_sweep"] = {
            lbl[3:]: {k: v[k] for k in ("next_token_ms", "first_token_ms",
                                        "kv_cache_bytes") if k in v}
            for lbl, v in ab_results.items() if lbl.startswith("kv-")}
    if not ok:
        record["note"] = ("every dispatch configuration failed or was "
                          "rejected by the physics floors")
        print(json.dumps(record))
        raise SystemExit(1)
    # the HEADLINE is the SHIPPED DEFAULT config when it is valid (no
    # per-phase/per-config best-of as the record); a faster non-default
    # config is surfaced separately as the signal to change the default
    fastest = min(ok, key=lambda k: ok[k]["next_token_ms"])
    best = "pallas+gemv" if "pallas+gemv" in ok else fastest
    first_ms = ok[best]["first_token_ms"]
    next_ms = ok[best]["next_token_ms"]

    record.update(
        value=round(next_ms, 3),
        vs_baseline=round(BASELINE_NEXT_TOKEN_MS / next_ms, 3),
        valid=True,
        first_token_ms=round(first_ms, 3),
        best_config=best,
        prepack=ok[best].get("prepack"),
        observability=ok[best].get("observability", {}),
    )
    if fastest != best:
        record["fastest_config"] = fastest
        record["fastest_next_token_ms"] = round(
            ok[fastest]["next_token_ms"], 3)
    record.update(_roofline_block(
        LLAMA2_7B, ok[best]["weight_bytes"], PROMPT_LEN, DECODE_STEPS,
        first_ms, next_ms,
        kv_cache_dtype=ok[best].get("kv_cache_dtype", "bf16"),
        device_kind=device["kind"]))
    print(json.dumps(record))
    failed = sorted(k for k, v in ab_results.items() if "error" in v)
    if failed:
        # the record above stands for the lanes that ran; exit 2 says
        # some lanes have no numbers (exit 1: none has)
        print(f"bench: {len(failed)} lane(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        raise SystemExit(2)


def _efficiency(cfg, weight_bytes: int, prompt_len: int, steps: int,
                first_ms: float, next_ms: float,
                device_kind: "str | None" = None) -> dict:
    """MFU + HBM-roofline utilization.

    Decode on one chip is HBM-bandwidth-bound: every token reads the whole
    packed weight set plus the live KV slice, so the honest efficiency
    number is bytes-moved / (latency x peak-BW). Prefill is compute-bound,
    so its number is model FLOPs / (latency x peak-FLOPs) — classic MFU.
    The math lives in observability/roofline.py, shared with the
    engine's live gauges. `weight_bytes` is measured from the live param
    pytree in the config subprocess and passed through."""
    return _roofline.efficiency(cfg, weight_bytes, prompt_len, steps,
                                first_ms, next_ms,
                                device_kind=device_kind)


def _roofline_block(cfg, weight_bytes: int, prompt_len: int, steps: int,
                    first_ms: float, next_ms: float,
                    kv_cache_dtype: str = "bf16",
                    device_kind: "str | None" = None) -> dict:
    """The headline record's efficiency numbers plus the per-phase
    roofline attribution block (analytical FLOPs / HBM bytes / ideal ms
    next to the measured ms) — both from observability/roofline.py."""
    out = _efficiency(cfg, weight_bytes, prompt_len, steps,
                      first_ms, next_ms, device_kind=device_kind)
    out["roofline"] = _roofline.attribution(
        cfg, weight_bytes, prompt_len, steps, first_ms, next_ms,
        kv_cache_dtype=kv_cache_dtype, device_kind=device_kind)
    return out


def _parse_kv_sweep(argv: "list[str]") -> "list[str] | None":
    """`--kv-cache-dtype a,b,c` (or `=`-joined) -> validated dtype list."""
    from bigdl_tpu.ops.kvcache import resolve_kv_cache_dtype

    spec = None
    for i, a in enumerate(argv):
        if a == "--kv-cache-dtype" and i + 1 < len(argv):
            spec = argv[i + 1]
        elif a.startswith("--kv-cache-dtype="):
            spec = a.split("=", 1)[1]
    if spec is None:
        return None
    return [resolve_kv_cache_dtype(d) for d in spec.split(",") if d]


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--config":
        _one_config(sys.argv[2])
    else:
        main(kv_sweep=_parse_kv_sweep(sys.argv[1:]))
