"""Per-token generation timing: the BenchmarkWrapper equivalent.

The reference forks HF's generate to time every token
(dev/benchmark/benchmark_util.py:489-520 `BenchmarkWrapper`, metrics
`first_cost`/`rest_cost_mean`/`peak_memory` at :2447-2476, injected into
serving via env in transformers/loader.py:43-77). Here the model already
owns its generate loop, so the wrapper simply drives it with a
GenerationStats collector and reads device memory stats from JAX.

Note on TPU timing: every host sync pays a dispatch + readback cost;
`rest_cost_mean` measured around a host-step loop includes it. For
device-only numbers use `timed_decode` (K steps inside one jit,
differenced).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from bigdl_tpu.generation import GenerationStats


def device_peak_memory() -> Optional[int]:
    """Peak device memory in bytes (None if the backend has no stats)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats:
            return int(stats.get("peak_bytes_in_use",
                                 stats.get("bytes_in_use", 0)))
    except Exception:
        pass
    return None


@dataclasses.dataclass
class BenchmarkResult:
    first_cost: float              # seconds, prompt -> first token
    rest_cost_mean: float          # seconds per subsequent token
    n_tokens: int
    peak_memory: Optional[int]     # bytes

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class BenchmarkWrapper:
    """Wrap a TpuCausalLM: `.generate()` passes through, timings recorded.

    >>> m = BenchmarkWrapper(model)
    >>> out = m.generate(ids, max_new_tokens=32)
    >>> m.results[-1].first_cost, m.results[-1].rest_cost_mean
    """

    def __init__(self, model: Any, do_print: bool = False):
        self.model = model
        self.do_print = do_print
        self.results: List[BenchmarkResult] = []

    def __getattr__(self, name):
        return getattr(self.model, name)

    def generate(self, input_ids, **kw):
        stats = GenerationStats()
        kw["stats"] = stats
        out = self.model.generate(input_ids, **kw)
        n = len(stats.rest_token_s) + 1
        res = BenchmarkResult(
            first_cost=stats.first_token_s,
            rest_cost_mean=stats.rest_cost_mean,
            n_tokens=n,
            peak_memory=device_peak_memory(),
        )
        self.results.append(res)
        if self.do_print:
            pm = (f"{res.peak_memory / 2**30:.2f} GB"
                  if res.peak_memory else "n/a")
            print(f"=========== BENCHMARK: first={res.first_cost*1e3:.1f} ms "
                  f"rest_mean={res.rest_cost_mean*1e3:.2f} ms "
                  f"tokens={res.n_tokens} peak_mem={pm} ===========")
        return out
