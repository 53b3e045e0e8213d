"""Quantized linear on the chip: the Pallas dequant GEMM against the XLA
dequantize-then-dot plan, per shape and row count, device time from a
profiler trace. The table behind `ops/matmul.PALLAS_MAX_ROWS`.

    chiprun -- python3 tools/qmatmul_ab.py [--shapes mistral,chatglm2,deepseek]
        [--rows 256,512,1024,2048,8192] [--qtype sym_int4] [--prepack on|off]
        [--out chiprun_out/qmatmul_ab.json]
    chiprun -- python3 tools/qmatmul_ab.py --layer-scan [--shapes mistral]
        [--rows 32,256]

Each case is a program of its own (`jit_ab_<backend>_<K>x<N>_m<M>`): a
`lax.scan` over LAYERS stacked copies of the weight, as a model's layer
scan reads them, so the per-layer slice out of the stack that a kernel's
operand costs is inside the time. Every program runs RUNS times inside
one trace; a case's time is the median duration of its program on the
device's "XLA Modules" line over LAYERS. Without a TPU it exits 3: a
CPU time is no measurement.

`--layer-scan` times a family's linears of one layer together, inside a
scan over SCAN_LAYERS layers as a model's forward runs them, twice: the
stacks scanned BY VALUE (XLA writes each layer's slice before the kernel
reads it: the scanned families before PR 46) and read IN PLACE
(`ops/matmul.StackedQ`: the kernel addresses the layer inside the
stack). `ms` is the program's time a layer, `kernel_ms` the kernels'
alone; their gap is what the slices cost.
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
SCAN_LAYERS = 32
RUNS = 3

# [K, N] as the three benchmark configurations build them (merged QKV
# and gate/up for the llama tree; DeepSeek-V2's dense linears)
SHAPES = {
    "mistral": [(4096, 6144), (4096, 4096), (4096, 28672), (14336, 4096)],
    "chatglm2": [(4096, 4608), (4096, 27392), (13696, 4096)],
    "deepseek": [(5120, 1536), (1536, 24576), (5120, 640), (16384, 5120),
                 (5120, 3072), (3072, 5120), (5120, 12288), (12288, 5120)],
}


def stacked_weight(k: int, n: int, qtype: str, prepack: str = "on",
                   layers: int = LAYERS):
    """[layers, K, N] QTensor in the layout a TPU load gives the qtype
    (int4-dtype codes for sym_int4; `prepack` "off" keeps the canonical
    split-block nibbles)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.quant import prepack_tree, quantize

    w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32) * 0.02
    qt, _ = prepack_tree(jax.jit(lambda a: quantize(a, qtype))(w), prepack)
    return jax.tree.map(lambda a: jnp.stack([a] * layers), qt)


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


def layer_scan_program(in_place: bool, name: str):
    """A scan over the layers of `stacks` (one `[L, K, N]` QTensor a
    linear), every linear of a layer on the carry: the stacks scanned by
    value, or closed over and read at the layer index. A linear's input
    is the carry cut or tiled to its K; its output feeds the carry
    through a row sum, as in `program`."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.matmul import StackedQ, q_matmul

    def run(x, stacks):
        d = x.shape[1]

        def layer(c, ws):
            for w, full in zip(ws, stacks):
                k = full.shape[0]
                a = jnp.tile(c, (1, -(-k // d)))[:, :k]
                y = q_matmul(a, w)
                c = c + (jnp.sum(y.astype(jnp.float32), -1, keepdims=True)
                         * 1e-9).astype(c.dtype)
            return c, None

        if not in_place:
            return jax.lax.scan(layer, x, stacks)[0]
        return jax.lax.scan(
            lambda c, i: layer(c, [StackedQ(s, i) for s in stacks]), x,
            jnp.arange(SCAN_LAYERS, dtype=jnp.int32))[0]

    run.__name__ = name
    return jax.jit(run)


def measure(cases, layers: int = LAYERS):
    """`cases`: [(label dict, jitted program, args)]. Runs every program
    RUNS times under one trace; returns the label dicts with `ms` (median
    program time on the device over `layers`) and `kernel_ms` (the same
    for the qmatmul custom calls inside it, None where there is none)."""
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
            ms=statistics.median(ev[2] for ev in runs) / 1e6 / layers,
            kernel_ms=(statistics.median(inside) / 1e6 / layers
                       if any(inside) else None)))
    return out


def shape_table(args, rows):
    """Kernel against XLA plan, one weight shape at a time."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.matmul import kernel_plan, q_matmul
    from bigdl_tpu.ops.quant import get_qtype

    shapes = sorted({s for fam in args.shapes.split(",") for s in SHAPES[fam]})
    block = get_qtype(args.qtype).block_size
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
        yield from measure(cases)
        del ws, cases


def layer_scan_table(args, rows):
    """A family's linears of one layer in a SCAN_LAYERS-layer scan, the
    stacks by value against in place (auto dispatch, as a model)."""
    import jax
    import jax.numpy as jnp

    for fam in args.shapes.split(","):
        stacks = [stacked_weight(k, n, args.qtype, args.prepack, SCAN_LAYERS)
                  for k, n in SHAPES[fam]]
        cases = []
        for m in rows:
            x = jax.random.normal(jax.random.PRNGKey(m),
                                  (m, SHAPES[fam][0][0]), jnp.bfloat16)
            for mode in ("by_value", "in_place"):
                fn = layer_scan_program(mode == "in_place",
                                        f"ab_scan_{mode}_{fam}_m{m}")
                cases.append((dict(family=fam, m=m, mode=mode), fn,
                              (x, stacks)))
        yield from measure(cases, SCAN_LAYERS)
        del stacks, cases


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,chatglm2,deepseek")
    ap.add_argument("--rows", default="256,512,1024,2048,8192")
    ap.add_argument("--qtype", default="sym_int4")
    ap.add_argument("--prepack", default="on", choices=("on", "off"))
    ap.add_argument("--out", default="chiprun_out/qmatmul_ab.json")
    ap.add_argument("--layer-scan", action="store_true",
                    help="a layer's linears in a scan over SCAN_LAYERS "
                         "layers, by value against in place")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU"}))
        return 3
    rows = [int(r) for r in args.rows.split(",")]
    table = []
    for row in (layer_scan_table if args.layer_scan else shape_table)(
            args, rows):
        print(json.dumps(row), flush=True)
        table.append(row)
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"device": jax.devices()[0].device_kind, "qtype": args.qtype,
         "prepack": args.prepack,
         "layers": SCAN_LAYERS if args.layer_scan else LAYERS,
         "runs": RUNS, "table": table}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
