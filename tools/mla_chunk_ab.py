"""A latent layer's chunk attention on the chip: the Pallas kernel
`mla_chunk_attention` against the XLA sweep it replaces
(`models/dots3_note._sweep_chunk`), at the sparse-latent cells' geometry
(128 heads x (128 + 64), latent rows 512 + 64, 1024 rows a chunk, half
the causal keys selected), by the keys already cached.

    chiprun -- python3 tools/mla_chunk_ab.py [--cases 8192:0,8192:7168,16384:15360]
        [--out chiprun_out/mla_chunk_ab.json]

A case `S:p` is a chunk at first position `p` over a private cache of
`S` positions; each side is one program of LAYERS calls on a five-layer
stack, timed as the least of RUNS waits on `block_until_ready` over
LAYERS: `xla_ms` and `kernel_ms` a layer, and the largest difference
between the two outputs. Without a TPU it exits 3: a CPU time is no
measurement.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

LAYERS = 5
RUNS = 5
H, C, R, NOPE, VD, T = 128, 512, 64, 128, 128, 1024


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="8192:0,8192:7168,16384:9216,"
                    "16384:15360")
    ap.add_argument("--out", default="chiprun_out/mla_chunk_ab.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("no TPU: a CPU time is no measurement", file=sys.stderr)
        return 3
    from bigdl_tpu.models import dots3_note
    from bigdl_tpu.ops.pallas.mla_chunk_attention import \
        mla_chunk_attention_pallas

    kind = dots3_note.MlaKind(H, 1024, C, NOPE, R, VD, 8e7, 1e-5, None, None)
    rng = np.random.default_rng(0)

    def bf(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)

    qn, qp = bf(1, T, H, NOPE), bf(1, T, H, R)
    w_uk, w_uv = bf(H, NOPE, C, scale=0.05), bf(H, C, VD, scale=0.05)

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        waits = []
        for _ in range(RUNS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            waits.append(time.perf_counter() - t0)
        return out, 1e3 * min(waits) / LAYERS

    # every operand an argument: a closed-over array is a constant of
    # the executable
    @jax.jit
    def xla(qn, qp, w_uk, w_uv, lat, sel, pos):
        return sum(jax.vmap(lambda a, b, la, se, p: dots3_note._sweep_chunk(
            kind, a, b, la, se, p, w_uk, w_uv))(qn, qp, lat[i], sel, pos)
            for i in range(LAYERS))

    @jax.jit
    def kernel(qn, qp, w_uk, w_uv, lat, sel, pos):
        return sum(mla_chunk_attention_pallas(
            qn, qp, lat, pos, sel, w_uk, w_uv, kind.scale, layer=i)
            for i in range(LAYERS))

    table = []
    for case in args.cases.split(","):
        s, p = (int(x) for x in case.split(":"))
        lat = bf(LAYERS, 1, C + R, s)
        pos = jnp.asarray([p], jnp.int32)
        sel = ((jnp.arange(s)[None, None, :]
                <= p + jnp.arange(T)[None, :, None])
               & jnp.asarray(rng.random((1, T, s)) < 0.5))
        want, xla_ms = timed(xla, qn, qp, w_uk, w_uv, lat, sel, pos)
        got, kernel_ms = timed(kernel, qn, qp, w_uk, w_uv, lat, sel, pos)
        row = {"s": s, "p": p, "live_blocks": -(-(p + T) // 1024),
               "xla_ms": xla_ms, "kernel_ms": kernel_ms,
               "max_abs_diff": float(jnp.max(jnp.abs(got - want))),
               "max_abs": float(jnp.max(jnp.abs(want)))}
        print(json.dumps(row), flush=True)
        table.append(row)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind, "layers": LAYERS,
        "runs": RUNS, "table": table}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
