"""Quantized linear on the chip: the Pallas dequant GEMM against the XLA
dequantize-then-dot plan, per shape and row count, device time from a
profiler trace. The table behind `ops/matmul.PALLAS_MAX_ROWS`.

    chiprun -- python3 tools/qmatmul_ab.py [--shapes mistral,chatglm2,deepseek]
        [--rows 256,512,1024,2048,8192] [--qtype sym_int4] [--prepack on|off]
        [--out chiprun_out/qmatmul_ab.json]

Each case is a program of its own (`jit_ab_<backend>_<K>x<N>_m<M>`): a
`lax.scan` over LAYERS stacked copies of the weight, as a model's layer
scan reads them, so the per-layer slice out of the stack that a kernel's
operand costs is inside the time. Every program runs RUNS times inside
one trace; a case's time is the median duration of its program on the
device's "XLA Modules" line over LAYERS. Without a TPU it exits 3: a
CPU time is no measurement.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

LAYERS = 4
RUNS = 3

# [K, N] as the three benchmark configurations build them (merged QKV
# and gate/up for the llama tree; DeepSeek-V2's dense linears)
SHAPES = {
    "mistral": [(4096, 6144), (4096, 4096), (4096, 28672), (14336, 4096)],
    "chatglm2": [(4096, 4608), (4096, 27392), (13696, 4096)],
    "deepseek": [(5120, 1536), (1536, 24576), (5120, 640), (16384, 5120),
                 (5120, 3072), (3072, 5120), (5120, 12288), (12288, 5120)],
}


def stacked_weight(k: int, n: int, qtype: str, prepack: str = "on"):
    """[LAYERS, K, N] QTensor in the layout a TPU load gives the qtype
    (int4-dtype codes for sym_int4; `prepack` "off" keeps the canonical
    split-block nibbles)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.quant import prepack_tree, quantize

    w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32) * 0.02
    qt, _ = prepack_tree(jax.jit(lambda a: quantize(a, qtype))(w), prepack)
    return jax.tree.map(lambda a: jnp.stack([a] * LAYERS), qt)


def program(matmul, name: str):
    """The scanned program of one case; `matmul(x, w)` is the linear
    under test. Each layer's output feeds the next layer's input through
    a row sum, so no layer can be dropped or reordered."""
    import jax
    import jax.numpy as jnp

    def run(x, ws):
        def layer(c, w):
            y = matmul(c, w)
            bump = jnp.sum(y.astype(jnp.float32), -1, keepdims=True) * 1e-9
            return c + bump.astype(c.dtype), None

        return jax.lax.scan(layer, x, ws)[0]

    run.__name__ = name
    return jax.jit(run)


def measure(cases):
    """`cases`: [(label dict, jitted program, args)]. Runs every program
    RUNS times under one trace; returns the label dicts with `ms` (median
    program time on the device over LAYERS) and `kernel_ms` (the same for
    the qmatmul custom calls inside it, None where there is none)."""
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
    # one event per run, in the order the programs ran (not by name: the
    # compile cache may hand two cases one executable, under one name)
    modules = sorted((ev for ev in lines[trace_reduce.MODULES_LINE]
                      if "jit_ab_" in ev[0]), key=lambda ev: ev[1])
    if len(modules) != RUNS * len(cases):
        raise RuntimeError(f"{len(modules)} program runs in the trace for "
                           f"{len(cases)} cases x {RUNS}")
    kernels = [ev for ev in lines[trace_reduce.OPS_LINE] if "qmatmul" in ev[0]]
    out = []
    for i, (label, _, _) in enumerate(cases):
        runs = modules[i * RUNS:(i + 1) * RUNS]
        inside = [sum(k[2] for k in kernels
                      if ev[1] <= k[1] < ev[1] + ev[2]) for ev in runs]
        out.append(dict(
            label,
            ms=statistics.median(ev[2] for ev in runs) / 1e6 / LAYERS,
            kernel_ms=(statistics.median(inside) / 1e6 / LAYERS
                       if any(inside) else None)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,chatglm2,deepseek")
    ap.add_argument("--rows", default="256,512,1024,2048,8192")
    ap.add_argument("--qtype", default="sym_int4")
    ap.add_argument("--prepack", default="on", choices=("on", "off"))
    ap.add_argument("--out", default="chiprun_out/qmatmul_ab.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU"}))
        return 3
    from bigdl_tpu.ops.matmul import kernel_plan, q_matmul
    from bigdl_tpu.ops.quant import get_qtype

    rows = [int(r) for r in args.rows.split(",")]
    shapes = sorted({s for fam in args.shapes.split(",") for s in SHAPES[fam]})
    block = get_qtype(args.qtype).block_size
    table = []
    for k, n in shapes:
        ws = stacked_weight(k, n, args.qtype, args.prepack)
        mxu = ws.data.dtype == jnp.int4
        kp = ws.scale.shape[-2] * block
        cases = []
        for m in rows:
            x = jax.random.normal(jax.random.PRNGKey(m), (m, k), jnp.bfloat16)
            for be in ("xla", "pallas"):
                if be == "pallas" and kernel_plan(
                        args.qtype, m, kp, n, mxu) is None:
                    continue                      # no legal tiling: a rule
                fn = program(
                    lambda a, w, be=be: q_matmul(a, w, backend=be),
                    f"ab_{be}_{k}x{n}_m{m}")
                cases.append((dict(k=k, n=n, m=m, backend=be), fn, (x, ws)))
        for row in measure(cases):
            print(json.dumps(row), flush=True)
            table.append(row)
        del ws, cases
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"device": jax.devices()[0].device_kind, "qtype": args.qtype,
         "prepack": args.prepack,
         "layers": LAYERS, "runs": RUNS, "table": table}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
