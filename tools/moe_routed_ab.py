"""The routed decode layer on the chip, kernels alone: the plan before
PR 54 (gate, up and down as three `routed_expert_matmul` calls at
`routed_tiles`' power-of-two tiles, the activation, the mask and the sum
over experts in XLA ops between and after them) against the decode pair
(`routed_gate_up` + `routed_down_sum` at `decode_tiles`' tiles), at the
six routed cells' `(held, hit, T, D, F)`.

    chiprun -- python3 tools/moe_routed_ab.py [--cases sdar,trinity,...]
        [--budget-mb 24] [--chunk-k 256] [--unroll 1] [--out chiprun_out/moe_routed_ab.json]

`--cases sdar:19:19` runs a case at another `held:hit` (no idle tile:
what the tiles past the hit experts cost). A case is a program a side (`jit_ab_<side>_<case>`): a `lax.scan` over
LAYERS layers of `[L, held, ...]` sym_int4 stacks read where they lie,
`hit` of the held experts chosen by some token. Every program runs RUNS
times inside one trace; `us` is the median program time on the device a
layer, `kernel_us` that of the layer's own operations (the kernels and
the XLA ops between them) and `calls` the us of each by name, a name's
calls in the order they ran (the parent's three: gate, up, down). `--budget-mb` / `--chunk-k` / `--unroll` (lists) run the new
side once a value of `DECODE_VMEM_BUDGET` / `DECODE_CHUNK_ELEMS` /
`DECODE_CHUNK_UNROLL`: the sweep behind the module's constants. Without a TPU it exits 3: a CPU time is
no measurement.
"""

from __future__ import annotations

import argparse
import itertools
import json
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

LAYERS = 4
RUNS = 5

# (held, hit, T, D, F): each cell's share of the experts, the held
# experts a layer-step hits (ledger, PR 53: `moe_experts_hit_share`), the
# decode program's rows padded to 16, and the published expert widths
CASES = {
    "sdar": (32, 19, 64, 2048, 768),
    "trinity": (32, 18, 16, 2048, 1024),
    "mimo": (32, 15, 16, 4096, 2048),
    "deepseekv2": (20, 14, 32, 5120, 1536),
    "dots3": (32, 12, 16, 5120, 1536),
    "deepseekv32": (32, 13, 16, 7168, 2048),
}


def stacks(key, held: int, d: int, f: int):
    """Random canonical sym_int4 `[LAYERS, held, ...]` gate, up and down
    stacks: uniform nibbles, bf16 scales near a trained layer's."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.quant import QTensor, get_qtype

    b = get_qtype("sym_int4").block_size

    def one(kk, k, n):
        k1, k2 = jax.random.split(kk)
        data = jnp.stack([jax.random.bits(kl, (held, k // 2, n), jnp.uint8)
                          for kl in jax.random.split(k1, LAYERS)])
        scale = jax.random.uniform(k2, (LAYERS, held, k // b, n),
                                   jnp.float32, 0.002, 0.006)
        return QTensor(data, scale.astype(jnp.bfloat16), None, "sym_int4",
                       (k, n))

    kg, ku, kd = jax.random.split(key, 3)
    return one(kg, d, f), one(ku, d, f), one(kd, f, d)


def parent_decode(x1, cw, order, n_hit, gate, up, down, layer):
    """`ops/moe_routed._decode` as it stood before PR 54."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.pallas.moe_routed import (DECODE_NAME,
                                                 routed_expert_matmul)

    mm = lambda x, w, shared: routed_expert_matmul(             # noqa: E731
        x, w, order, n_hit, layer, name=DECODE_NAME, shared_x=shared)
    live = (jnp.arange(cw.shape[0]) < n_hit)[:, None, None]
    h = (jax.nn.silu(mm(x1, gate, True).astype(jnp.float32))
         * mm(x1, up, True).astype(jnp.float32) * cw[..., None])
    h = jnp.where(live, h, 0.0).astype(x1.dtype)
    y = jnp.where(live, mm(h, down, False).astype(jnp.float32), 0.0)
    return jnp.sum(y, axis=0).astype(x1.dtype)


def new_decode(x1, cw, order, n_hit, gate, up, down, layer):
    import jax

    from bigdl_tpu.ops.pallas.moe_routed import (routed_down_sum,
                                                 routed_gate_up)

    h = routed_gate_up(x1, gate, up, cw, order, n_hit, layer,
                       act=jax.nn.silu)
    return routed_down_sum(h, down, order, n_hit, layer)


def program(decode, name: str):
    """LAYERS routed layers in a scan, each layer's output the next one's
    input (through a row sum, so none can be dropped)."""
    import jax
    import jax.numpy as jnp

    def run(x1, cw, order, n_hit, gate, up, down):
        def layer(c, i):
            y = decode(c, cw, order, n_hit, gate, up, down, i)
            bump = jnp.sum(y.astype(jnp.float32), -1, keepdims=True) * 1e-9
            return c + bump.astype(c.dtype)[None], y

        return jax.lax.scan(layer, x1, jnp.arange(LAYERS, dtype=jnp.int32))

    run.__name__ = name
    return jax.jit(run)


def compiled(decode, name: str, ops):
    """The case's executable, compiled now: the plan is read from the
    module's constants at trace time and is no part of a jit's key."""
    import jax

    jax.clear_caches()
    return program(decode, name).lower(*ops).compile()


def measure(cases):
    """`cases`: [(label, jitted program, args)] -> the labels with `us`
    (median program time a layer), `kernel_us` (its `moe_routed_*`
    kernels) and `calls` (us a call by kernel name)."""
    import jax

    from harness import trace_reduce

    for _, fn, args in cases:
        jax.block_until_ready(fn(*args))          # compile + warm
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _, fn, args in cases:
            for _ in range(RUNS):
                jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        planes = trace_reduce.load(trace_reduce.find_xplane(Path(d)))
    dev = next(p for p in planes if trace_reduce.DEVICE_PLANE.match(p["name"]))
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    modules = sorted((ev for ev in lines[trace_reduce.MODULES_LINE]
                      if "jit_ab_" in ev[0]), key=lambda ev: ev[1])
    if len(modules) != RUNS * len(cases):
        raise RuntimeError(f"{len(modules)} program runs in the trace for "
                           f"{len(cases)} cases x {RUNS}")
    kernels = [ev for ev in lines[trace_reduce.OPS_LINE]
               if "moe_routed" in ev[0]]
    out = []
    for i, (label, _, _) in enumerate(cases):
        runs = modules[i * RUNS:(i + 1) * RUNS]
        inside = [[k for k in kernels if ev[1] <= k[1] < ev[1] + ev[2]]
                  for ev in runs]
        calls = {}
        for k in inside[RUNS // 2]:
            calls.setdefault(k[0].lstrip("%").split(".")[0], []).append(
                k[2] / 1e3)
        out.append(dict(
            label,
            us=statistics.median(ev[2] for ev in runs) / 1e3 / LAYERS,
            kernel_us=statistics.median(
                sum(k[2] for k in ks) for ks in inside) / 1e3 / LAYERS,
            # a name's calls of one layer in the order they ran (the
            # parent's `moe_routed_decode`: gate, up, down), each the
            # median over the layers
            calls={n: [round(statistics.median(v[p::len(v) // LAYERS]), 2)
                       for p in range(len(v) // LAYERS)]
                   for n, v in calls.items()}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--budget-mb", default="")
    ap.add_argument("--chunk-k", default="")
    ap.add_argument("--unroll", default="")
    ap.add_argument("--out", default="chiprun_out/moe_routed_ab.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("no TPU: a CPU time is no measurement", file=sys.stderr)
        return 3
    from bigdl_tpu.ops.pallas import moe_routed as kernels

    budgets = [int(float(v) * 2 ** 20) for v in args.budget_mb.split(",")
               if v] or [kernels.DECODE_VMEM_BUDGET]
    chunks = [int(v) * 1024 for v in args.chunk_k.split(",")
              if v] or [kernels.DECODE_CHUNK_ELEMS]
    unrolls = [int(v) for v in args.unroll.split(",")
               if v] or [kernels.DECODE_CHUNK_UNROLL]
    table = []
    for name in args.cases.split(","):
        name, *share = name.split(":")          # `case:held:hit`
        held, hit, t, d, f = CASES[name]
        if share:
            held, hit = (int(v) for v in share)
        rng = np.random.default_rng(0)
        gate, up, down = stacks(jax.random.PRNGKey(1), held, d, f)
        x1 = jnp.asarray(rng.standard_normal((1, t, d), np.float32),
                         jnp.bfloat16)
        order = jnp.asarray(rng.permutation(held), jnp.int32)
        cw = jnp.asarray(rng.random((held, t), np.float32)
                         * (rng.random((held, t)) < 0.25))
        ops = (x1, cw, order, jnp.int32(hit), gate, up, down)
        label = dict(case=name, held=held, hit=hit, t=t, d=d, f=f)
        cases = [(dict(label, side="parent",
                       tiles=[kernels.routed_tiles("sym_int4", d, f),
                              kernels.routed_tiles("sym_int4", f, d)]),
                  compiled(parent_decode, f"ab_parent_{name}", ops), ops)]
        for budget, chunk, unroll in itertools.product(budgets, chunks,
                                                       unrolls):
            kernels.DECODE_VMEM_BUDGET = budget
            kernels.DECODE_CHUNK_ELEMS = chunk
            kernels.DECODE_CHUNK_UNROLL = unroll
            fn = compiled(
                new_decode,
                f"ab_new_{name}_{budget >> 20}m_{chunk >> 10}k_u{unroll}", ops)
            cases.append((dict(
                label, side="new", budget_mb=budget / 2 ** 20,
                chunk_k=chunk // 1024, unroll=unroll,
                tiles=[kernels.decode_tiles("sym_int4", d, f, t, 2),
                       kernels.decode_tiles("sym_int4", f, d, t)]), fn, ops))
        want = cases[0][1](*ops)[1].astype(jnp.float32)
        rows = measure(cases)
        for row, (_, fn, _) in zip(rows, cases):
            got = fn(*ops)[1].astype(jnp.float32)
            row["rel_l2_to_parent"] = float(
                jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
            print(json.dumps(row), flush=True)
            table.append(row)
        del gate, up, down, cases, ops
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind, "layers": LAYERS,
        "runs": RUNS, "table": table}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
