"""Self-speculative decoding benchmark: spec-vs-plain on one chip.

The reference claims ~30% latency reduction from self-speculation
(reference README.md:18, "as fast as 33.7 ms/token with Self-Speculative
Decoding" vs ~48 ms fp16 plain); this measures the analog: llama2-7B,
sym_int8 target + sym_int4 draft (the self-speculation pairing closest
to the reference's fp16+int4 that fits one v5e), plain greedy vs
speculative wall-clock over the same decode budget.

Caveat carried in the record: on RANDOM weights the draft and target
(two quantizations of the same tensor) agree almost always, so the
MEASURED acceptance is an upper bound; the record therefore also
reports the per-round mechanics (draft step time, verify time) and a
projected speedup at a realistic 80% acceptance, computed from the
measured round timings.

Run: python bench_speculative.py  — prints ONE JSON line like bench.py.
Needs a TPU: without one it prints `{"ok": false, ...}` and exits
non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench import chip_peaks, require_tpu

    device = require_tpu("bench_speculative")
    import jax

    from bigdl_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.generation import generate_on_device
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.speculative import (SpecStats, prompt_lookup_generate, speculative_generate)
    from bigdl_tpu.utils.testing import LLAMA2_7B, random_llama_params

    cfg = LLAMA2_7B
    prompt_len, new_tokens, max_seq = 256, 128, 1024
    gamma = 4

    target = random_llama_params(cfg, qtype="sym_int8", seed=0)
    draft = random_llama_params(cfg, qtype="sym_int4", seed=0)
    jax.block_until_ready(jax.tree_util.tree_leaves(target)[0])
    prompt = jnp.ones((1, prompt_len), jnp.int32)

    def plain_run():
        cache = llama_mod.new_cache(cfg, 1, max_seq)
        t0 = time.perf_counter()
        out, _ = generate_on_device(
            target, cfg, llama_mod.forward, prompt, cache,
            max_new_tokens=new_tokens)
        np.asarray(out)
        return time.perf_counter() - t0

    def spec_run():
        stats = SpecStats()
        t0 = time.perf_counter()
        out = speculative_generate(
            target, draft, cfg, cfg, prompt,
            family_forward=llama_mod.forward,
            family_prefill=llama_mod.forward_last_token,
            new_cache=llama_mod.new_cache,
            max_new_tokens=new_tokens, gamma=gamma, max_seq=max_seq,
            th_stop_draft=0.0, stats=stats)
        np.asarray(out)
        return time.perf_counter() - t0, stats

    def best_of(run, n=3):
        run()                         # compile
        best = None
        for _ in range(n):
            r = run()
            key = r[0] if isinstance(r, tuple) else r
            if best is None or key < (best[0] if isinstance(best, tuple)
                                      else best):
                best = r
        return best

    plain_s = best_of(plain_run)
    spec_s, stats = best_of(spec_run)

    plain_ms = plain_s / new_tokens * 1e3
    spec_ms = spec_s / new_tokens * 1e3
    accept = stats.accept_rate
    tokens_per_round = stats.mean_accept + 1.0
    round_ms = spec_s / max(len(stats.accepted), 1) * 1e3
    # projected: tokens/round at acceptance a = a*gamma + 1 (geometric
    # prefix accept approximated linearly, the standard projection)
    proj_ms_80 = round_ms / (0.8 * gamma + 1.0)

    speedup = plain_ms / spec_ms if spec_ms > 0 else 0.0
    # physics floor: a verify step reads the int8 weights once -> no
    # per-round time below weight_bytes/BW is real
    wb = sum(getattr(l, "nbytes", l.nbytes)
             for l in jax.tree_util.tree_leaves(target))
    _, peak_gbps = chip_peaks(device["kind"])
    floor_round_ms = wb / (peak_gbps * 1e9) * 1e3 * 0.8
    valid = bool(round_ms > floor_round_ms and spec_s > 0)

    # prompt-lookup leg: n-gram drafts, NO draft model (beyond both the
    # reference and the draft-model path above) — repetition-heavy
    # prompts are its habitat, so bench a repeated-pattern prompt
    lookup_gamma = 8
    rep = np.tile(np.arange(1, 17, dtype=np.int32),
                  prompt_len // 16)[None, :prompt_len]

    def lookup_run():
        st = SpecStats()
        t0 = time.perf_counter()
        out = prompt_lookup_generate(
            target, cfg, rep,
            family_forward=llama_mod.forward,
            family_prefill=llama_mod.forward_last_token,
            new_cache=llama_mod.new_cache,
            max_new_tokens=new_tokens, gamma=lookup_gamma, max_seq=max_seq,
            stats=st)
        np.asarray(out)
        return time.perf_counter() - t0, st

    lookup_s, lstats = best_of(lookup_run)
    lookup_ms = lookup_s / new_tokens * 1e3
    lookup_round_ms = lookup_s / max(lstats.rounds, 1) * 1e3
    lookup_valid = bool(lookup_round_ms > floor_round_ms)

    rec = {
        "metric": "llama2_7b_selfspec_decode_speedup",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup / 1.3, 3),   # reference ~30% claim
        "valid": valid,
        "device": device,
        "plain_ms_per_token": round(plain_ms, 3),
        "spec_ms_per_token": round(spec_ms, 3),
        "gamma": gamma,
        "accept_rate": round(accept, 4),
        "tokens_per_round": round(tokens_per_round, 3),
        "round_ms": round(round_ms, 3),
        "projected_ms_per_token_at_80pct_accept": round(proj_ms_80, 3),
        "note": ("random-weight acceptance is an upper bound; "
                 "projected_* uses measured round mechanics at 80% "
                 "acceptance"),
        "prompt_len": prompt_len,
        "decode_steps": new_tokens,
        "model": "llama2-7b",
        "prompt_lookup": {
            "ms_per_token": round(lookup_ms, 3),
            "speedup_vs_plain": round(plain_ms / lookup_ms, 3)
            if lookup_ms > 0 else 0.0,
            "accept_rate": round(lstats.accept_rate, 4),
            "rounds": lstats.rounds,
            "valid": lookup_valid,
            "gamma": lookup_gamma,
            "note": "repeated-pattern prompt (lookup's habitat); no "
                    "draft model loaded",
        },
    }
    print(json.dumps(rec))
    if not (valid and lookup_valid):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
