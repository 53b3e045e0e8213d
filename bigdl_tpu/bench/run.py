"""All-in-one benchmark runner: config-driven latency sweeps.

Equivalent of the reference's `dev/benchmark/all-in-one/run.py:66-124`
(YAML config with repo_id matrix, in_out_pairs like "1024-128", test_api
selection, CSV output). Differences: APIs here are the TPU framework's own
paths, and results also land as one JSON line per run for machine
consumption.

Config (YAML or JSON):
    model_paths: [/path/to/llama-2-7b]    # HF dir, low-bit dir, or .gguf
    low_bit: sym_int4                     # or a list for a qtype sweep
    in_out_pairs: ["32-32", "1024-128"]
    test_api: transformers_int4           # or a list; see TEST_APIS
    num_trials: 3
    warm_up: 1
Output: CSV-ish stdout table + list of result dicts.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, List

import numpy as np

from bigdl_tpu.bench.benchmark_util import BenchmarkWrapper


def load_config(path: str) -> Dict[str, Any]:
    text = open(path).read()
    if path.endswith(".json"):
        return json.loads(text)
    import yaml

    return yaml.safe_load(text)


# test_api matrix (the reference's 20+ all-in-one modes collapse here:
# its matrix is mostly device/OS duplicates of the same four code paths
# — ours are distinct FRAMEWORK paths). Every mode measures
# BenchmarkWrapper-style first/rest latency unless noted.
TEST_APIS = (
    "transformers_int4",      # default generate (merged projections)
    "transformers_low_bit",   # alias; low_bit taken from the config
    "no_merge",               # split-projection layout A/B
    "fp8_kv",                 # e5m2-quantized KV cache
    "int8_kv",                # block-scaled int8 KV cache
    "int4_kv",                # block-scaled int4 KV cache
    "speculative",            # self-speculative decoding
    "serving",                # LLMEngine continuous batching: tokens/s
    "explicit_tp",            # shard_map TP over all local devices
    "gspmd_tp",               # GSPMD-sharded params, same generate
)


def _load(model_path, low_bit, max_seq, api):
    from bigdl_tpu.transformers.model import AutoModelForCausalLM

    kwargs: Dict[str, Any] = {}
    if api == "speculative":
        kwargs["speculative"] = True
    if api in ("no_merge", "explicit_tp"):
        # explicit TP shards the split layout; loading it directly
        # avoids a merge-then-unmerge round trip over every layer
        kwargs["merge_projections"] = False
    if api == "fp8_kv":
        kwargs["kv_cache_dtype"] = "fp8_e5m2"
    elif api.endswith("_kv"):
        kwargs["kv_cache_dtype"] = api[:-3]
    return AutoModelForCausalLM.from_pretrained(
        model_path, load_in_low_bit=low_bit, max_seq=max_seq, **kwargs)


def _bench_generate(model, prompt, out_len, num_trials, warm_up):
    bench = BenchmarkWrapper(model)
    firsts, rests = [], []
    for trial in range(warm_up + num_trials):
        bench.generate(prompt, max_new_tokens=out_len)
        res = bench.results[-1]
        if trial >= warm_up:
            firsts.append(res.first_cost)
            rests.append(res.rest_cost_mean)
    return {"first_token_ms": round(min(firsts) * 1e3, 3),
            "rest_token_ms": round(min(rests) * 1e3, 3),
            "peak_memory": bench.results[-1].peak_memory}


def _bench_serving(model, prompt, out_len, num_trials, warm_up):
    from bigdl_tpu.observability.metrics import MetricsRegistry
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    batch = 4
    # fresh registry per bench: the output rows report THIS run's
    # TTFT/TPOT distributions, not process-lifetime accumulation
    reg = MetricsRegistry()
    eng = LLMEngine(model, EngineConfig(
        max_batch=batch, max_seq=model.max_seq, prefix_cache_entries=0),
        registry=reg)
    prompts = [((prompt * (i + 3)) % model.config.vocab_size).tolist()
               for i in range(2 * batch)]
    sp = SamplingParams(max_tokens=out_len)
    for _ in range(max(warm_up, 1)):
        eng.generate(prompts[:batch], SamplingParams(max_tokens=2))
    best = 0.0
    for _ in range(max(num_trials, 1)):
        t0 = time.perf_counter()
        outs = eng.generate(prompts, sp)
        wall = time.perf_counter() - t0
        best = max(best, sum(len(o) for o in outs) / wall)
    summary = reg.summary()
    from bigdl_tpu.observability.compile_watch import compile_table

    out = {"serving_tokens_per_s": round(best, 2),
           "batch": batch, "requests": len(prompts),
           "observability": summary,
           "jit_compile_table": compile_table()}
    ttft = summary.get("bigdl_tpu_ttft_seconds")
    if isinstance(ttft, dict):
        out["ttft_p50_ms"] = round(ttft["p50"] * 1e3, 3)
    # the steps that carried no prefill chunk: the decode alone
    tpot = summary.get('bigdl_tpu_tpot_seconds{kind="plain"}')
    if isinstance(tpot, dict):
        out["tpot_p50_ms"] = round(tpot["p50"] * 1e3, 3)
    return out


def _bench_explicit_tp(model, prompt, out_len, num_trials, warm_up):
    import jax

    from jax.sharding import Mesh
    from bigdl_tpu.parallel.tp import shard_params_tp, tp_generate

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("tp",))
    params = shard_params_tp(model.params, mesh)
    best = None
    for trial in range(warm_up + num_trials):
        t0 = time.perf_counter()
        tp_generate(params, model.config, prompt[None], mesh,
                    max_new_tokens=out_len, max_seq=model.max_seq)
        wall = time.perf_counter() - t0
        if trial >= warm_up:
            best = wall if best is None else min(best, wall)
    return {"tp": n, "wall_ms": round(best * 1e3, 3),
            "per_token_ms": round(best * 1e3 / out_len, 3)}


def _bench_gspmd_tp(model, prompt, out_len, num_trials, warm_up):
    import jax

    from bigdl_tpu.parallel import make_mesh, shard_params

    n = len(jax.devices())
    mesh = make_mesh(tp=n)
    with mesh:
        model.params = shard_params(model.params, mesh)
        out = _bench_generate(model, prompt, out_len, num_trials, warm_up)
    out["tp"] = n
    return out


def run_one(model_path: str, low_bit: str, in_len: int, out_len: int,
            api: str, num_trials: int, warm_up: int,
            model=None) -> Dict[str, Any]:
    if api not in TEST_APIS:
        raise ValueError(f"unknown test_api {api!r}; choose from "
                         f"{TEST_APIS}")
    if model is None:
        max_seq = 1 << (in_len + out_len + 8 - 1).bit_length()
        model = _load(model_path, low_bit, max_seq, api)
    vocab = model.config.vocab_size
    prompt = (np.arange(1, in_len + 1, dtype=np.int32) * 977) % vocab

    harness = {"serving": _bench_serving,
               "explicit_tp": _bench_explicit_tp,
               "gspmd_tp": _bench_gspmd_tp}.get(api, _bench_generate)
    metrics = harness(model, prompt, out_len, num_trials, warm_up)
    if api == "speculative":
        # the spec drivers publish acceptance to the default registry
        # (speculative._spec_observe); surface it in the row
        from bigdl_tpu.observability.metrics import default_registry

        summary = default_registry().summary()
        acc = {k: v for k, v in summary.items()
               if k.startswith(("bigdl_tpu_spec_accept_ratio",
                                "bigdl_tpu_spec_tokens_total"))}
        if acc:
            metrics["observability"] = acc
    if "jit_compile_table" not in metrics:
        from bigdl_tpu.observability.compile_watch import compile_table

        metrics["jit_compile_table"] = compile_table()
    return {
        "model": model_path,
        "low_bit": low_bit,
        "api": api,
        "in_out": f"{in_len}-{out_len}",
        **metrics,
    }


def run(config: Dict[str, Any]) -> List[Dict[str, Any]]:
    rows = []
    apis = config.get("test_api", "transformers_int4")
    if isinstance(apis, str):
        apis = [apis]
    low_bits = config.get("low_bit", "sym_int4")
    if isinstance(low_bits, str):
        low_bits = [low_bits]
    bad = [a for a in apis if a not in TEST_APIS]
    if bad:
        # fail BEFORE any model load: a typo'd api must not cost a 7B
        # quantize
        raise ValueError(f"unknown test_api {bad}; choose from {TEST_APIS}")
    pairs = [tuple(int(x) for x in p.split("-"))
             for p in config.get("in_out_pairs", ["32-32"])]
    # one load per (model, api, low_bit) cell: in_out pairs reuse the
    # model (a 7B re-quantize per pair would double the run)
    max_seq = 1 << (max(i + o for i, o in pairs) + 8 - 1).bit_length()
    for model_path in config["model_paths"]:
        for api in apis:
            for low_bit in low_bits:
                model = _load(model_path, low_bit, max_seq, api)
                for in_len, out_len in pairs:
                    row = run_one(
                        model_path, low_bit, in_len, out_len, api,
                        int(config.get("num_trials", 3)),
                        int(config.get("warm_up", 1)),
                        model=model,
                    )
                    print(json.dumps(row))
                    rows.append(row)
    return rows


def main() -> None:
    cfg_path = sys.argv[1] if len(sys.argv) > 1 else "config.yaml"
    rows = run(load_config(cfg_path))
    if rows:
        # different apis report different metrics; the CSV carries the
        # column union with blanks
        cols = list(dict.fromkeys(c for r in rows for c in r))
        print(",".join(cols))
        for r in rows:
            print(",".join(str(r.get(c, "")) for c in cols))


if __name__ == "__main__":
    main()
