"""AFMoE (Trinity-Mini) held to its reference one block at a time, on the
SAME input, where the router's coin cannot fall.

Why. As ``checks_mimo_v2``: the model chooses 8 of 128 experts a token
and expert layer, thirty times a token; the program's hidden state
drifts a few per cent from the float32 reference's through the layers
and some token in most sequences has its eighth and ninth expert closer
than that, so the end-to-end comparisons hold garbage off and cannot see
a precision, a dropped gate or QK norm, a rotary where there is none, a
window half as long, a routing factor or a shared expert left out. This
check can: each block of a layer gets the reference's own input, rounded
to bfloat16 so that both sides read the same numbers, and its output
(BEFORE the branch's norm, which would divide a wrong scale away) is
held to the reference's for that input.

Which layers. One of every KIND the model holds, ``checked_layers``:
layer 0 (window, dense), layer 2 (window, experts) and layer 3 (full,
experts) of the published pattern. The other layers repeat these kinds
shape for shape on other seeded weights; the reference's stream goes
from one checked layer straight to the next.

What runs, at sizes where the mechanisms bind. A seeded sequence of
``PREFILL_ROWS + DECODE_ROWS`` tokens (3,072 + 8: three 1024-row chunks,
so the window drops positions inside the third chunk and for every
decoded row, and the 2048-column ring has wrapped) walks the reference;
at every layer checked

- attention: the program's ``attention_block`` prefills the first rows
  in the cell's chunks (``engine.prefill_chunk``) into a NEW one-layer
  private cache of the cell's length (``ops/swa.full_chunk`` over the
  live key blocks; ``window_chunk`` over the band, a window layer's rows
  in position order), splices it into a one-slot slab as
  ``engine_insert`` does (the last 2048 positions into the ring) and
  then takes the last rows one at a time through the slab with per-slot
  positions as the engine's (``decode_attention_lanes``;
  ``swa_decode_attention`` over the ring's two blocks);
- feed-forward: the program's ``moe_block`` (sigmoid router, the bias in
  the choice, ``route_scale``, ``routed_experts`` on the stacks where
  they lie, the shared expert) on the first rows as chunks and on the
  last rows as a batch of one-token slots; ``swiglu`` for a dense layer.

Compared: each reading against ``reference_afmoe.layer_limits``, the
LARGEST over the layers checked. ``stand_in`` puts something else in the
program's place through the same comparison: the reference with a
planted fault or a lower precision (``CONTROLS``).

As a command (``python3 benchmark/harness/checks_afmoe.py --config
<name> --seed n [--controls a,b] [--tiny]``) it runs the sound program
and then each control; each prints one line, and the last line says
whether every control came out NOT within the limits.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import Any, Dict

if __name__ == "__main__":      # run as a command: the harness is two up
    _bench = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_bench), str(_bench.parent)]

# the first layer of each pair (window?, routed?), the seeded ids, the
# verdict and the report are the window-and-full family's
from harness.checks_mimo_v2 import (KINDS, _within, check_ids,  # noqa: F401
                                    checked_layers, report)

PREFILL_ROWS = 3072
DECODE_ROWS = 8
_TYPES = {False: "full_attention", True: "sliding_attention"}
# name -> the reference's ``alter``: each must come out not within
CONTROLS = {
    "no_gate": {"gate": False},
    "no_qk_norm": {"qk_norm": False},
    "rotary_in_full": {"rotary_full": True},
    "window_halved": {"window": 0.5},
    "no_route_scale": {"route_scale": False},
    "no_shared_expert": {"shared": False},
    "ring_fp8_e5m2": {"ring_dtype": "float8_e5m2"},
}


def prefill_rows(max_seq: int) -> int:
    return min(PREFILL_ROWS, max_seq * 3 // 4)


class ProgramBlocks:
    """The program's blocks of one layer at a time, on the canonical
    tree: ``cfg`` the family's config, ``max_seq`` the slab's, ``chunk``
    the engine's prefill chunk."""

    def __init__(self, cfg, canonical: Dict[str, Any], max_seq: int,
                 chunk: int, kv: str = "bf16"):
        import jax

        from bigdl_tpu.models import afmoe as prog

        self.prog, self.cfg, self.canonical = prog, cfg, canonical
        self.max_seq, self.chunk, self.kv = max_seq, chunk, kv
        self.experts = canonical.get("experts")
        self._attn = jax.jit(
            lambda lp, y, cache, one, kind: prog.attention_block(
                y, lp, one, cache, kind), static_argnums=(3, 4))
        self._moe = jax.jit(
            lambda lp, experts, i, h: prog.moe_block(h, lp, experts, i,
                                                     cfg)[0])
        self._dense = jax.jit(lambda lp, h: prog.swiglu(
            h, lp["gate_proj"], lp["up_proj"], lp["down_proj"]))

    def _pieces(self, n: int):
        """``(start, stop)`` of the chunks and then the decoded rows."""
        p = prefill_rows(self.max_seq)
        return ([(a, min(a + self.chunk, p))
                 for a in range(0, p, self.chunk)]
                + [(t, t + 1) for t in range(p, n)])

    def attention(self, i: int, window_layer: bool, y):
        """Outputs ``[S, D]`` of the rows of ``y``."""
        import numpy as np

        from bigdl_tpu.ops.kvcache import init_cache_spec

        lp = self.prog.prepare_layer(
            self.prog.layer_leaves(self.canonical, self.cfg, i))
        one = dataclasses.replace(
            self.cfg, num_hidden_layers=1, num_dense_layers=1,
            layer_types=(_TYPES[window_layer],))
        kind = self.prog.WINDOW if window_layer else self.prog.FULL
        # as the engine: chunks into a private cache that keeps a window
        # layer's rows in position order, the splice into a one-slot
        # slab (the ring), the decoded rows through the slab
        spec = self.prog.cache_spec(one)
        cache = init_cache_spec(spec.unrolled(), 1, self.max_seq,
                                kv_cache_dtype=self.kv)
        p = prefill_rows(self.max_seq)
        rows = []
        for a, b in self._pieces(y.shape[0]):
            if a == p:
                cache = init_cache_spec(
                    spec, 1, self.max_seq, kv_cache_dtype=self.kv,
                    per_slot_pos=True).spliced(cache, 0, p)
            out, cache = self._attn(lp, y[None, a:b], cache, one, kind)
            rows.append(np.asarray(out[0], np.float32))
        return np.concatenate(rows)

    def feed_forward(self, i: int, h):
        import numpy as np

        lp = self.prog.layer_leaves(self.canonical, self.cfg, i)
        p = prefill_rows(self.max_seq)
        if not self.cfg.routed(i):
            run = lambda x: self._dense(lp, x)                 # noqa: E731
        else:
            at = i - self.cfg.num_dense_layers
            run = lambda x: self._moe(lp, self.experts, at, x)  # noqa: E731
        parts = [np.asarray(run(h[None, a:min(a + self.chunk, p)])[0],
                            np.float32) for a in range(0, p, self.chunk)]
        parts.append(np.asarray(run(h[p:, None])[:, 0], np.float32))
        return np.concatenate(parts)


class AlteredReference:
    """A control: the reference itself with ``alter`` (a planted fault
    or a precision below the configuration's) in the program's place."""

    def __init__(self, arch, quant, canonical, alter):
        import jax
        import jax.numpy as jnp

        from harness import reference_afmoe as reference

        alter = dict(alter)
        if isinstance(alter.get("window"), float):   # a share of the window
            alter["window"] = int(int(arch["window"]) * alter["window"])
        if isinstance(alter.get("ring_dtype"), str):
            alter["ring_dtype"] = jnp.dtype(alter["ring_dtype"])
        self.layers = {i: (lp, ex) for i, _, lp, ex
                       in reference.layer_stack(canonical, arch)
                       if i in checked_layers(arch)}
        self._attn = jax.jit(lambda y, lp, w: reference.attention(
            y, lp, arch, quant, w, alter), static_argnums=2)
        self._ff = jax.jit(lambda h, lp, ex: reference.feed_forward(
            h, lp, ex, arch, quant, alter))

    def attention(self, i: int, window_layer: bool, y):
        import jax
        import jax.numpy as jnp
        import numpy as np

        with jax.default_matmul_precision("highest"):
            return np.asarray(self._attn(y.astype(jnp.float32),
                                         self.layers[i][0], window_layer))

    def feed_forward(self, i: int, h):
        import jax
        import jax.numpy as jnp
        import numpy as np

        with jax.default_matmul_precision("highest"):
            return np.asarray(self._ff(h.astype(jnp.float32),
                                       *self.layers[i]))


def layer_errors(blocks, canonical: Dict[str, Any], arch: Dict[str, Any],
                 quant: Dict[str, Any], ids, n_prefill: int
                 ) -> Dict[str, Any]:
    """``blocks`` against the reference's blocks on the same inputs, in
    ``checked_layers``: for each reading the largest over those layers,
    and every layer's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import reference_afmoe as reference

    eps = float(arch["norm_eps"])
    norm = jax.jit(lambda x, w: reference._rms_norm(x, w, eps))
    attn = jax.jit(lambda y, lp, w: reference.attention(y, lp, arch, quant,
                                                        w), static_argnums=2)
    ff = jax.jit(lambda h, lp, ex: reference.feed_forward(h, lp, ex, arch,
                                                          quant))

    def ref(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    per_layer: Dict[str, list] = {}

    def hold(kind, got, want):
        want = np.asarray(want)
        for part, sl in (("prefill", slice(None, n_prefill)),
                         ("decode", slice(n_prefill, None))):
            per_layer.setdefault(f"{kind}_{part}", []).append(
                reference.relative_l2(got[sl], want[sl]))

    x = reference.embed(canonical, arch, ids)
    covered = checked_layers(arch)
    for i, window_layer, lp, ex in reference.layer_stack(canonical, arch):
        if i not in covered:
            continue     # the stream goes from checked layer to checked layer
        y = ref(norm, x, lp["input_layernorm"]).astype(jnp.bfloat16)
        a = ref(attn, y.astype(jnp.float32), lp, window_layer)
        hold(KINDS[window_layer], blocks.attention(i, window_layer, y), a)
        x = x + ref(norm, a, lp["post_attention_layernorm"])
        h = ref(norm, x, lp["pre_mlp_layernorm"]).astype(jnp.bfloat16)
        f = ref(ff, h.astype(jnp.float32), lp, ex)
        hold("ffn", blocks.feed_forward(i, h), f)
        x = x + ref(norm, f, lp["post_mlp_layernorm"])
    return {"found": {k: max(v) for k, v in per_layer.items()},
            "layers": per_layer, "checked_layers": covered}


def layer_check(config: Dict[str, Any], canonical: Dict[str, Any],
                seed: int, stand_in=None) -> Dict[str, Any]:
    """The check of ``config`` on the canonical tree of ``seed``: the
    program's blocks (or ``stand_in``) against the reference's, with the
    limits and the verdict."""
    import time

    from harness import reference_afmoe as reference
    from harness.weights import _family_config

    t_start = time.monotonic()
    arch, eng = config["reference"], config["engine"]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    max_seq = int(eng["max_seq"])
    n_prefill = prefill_rows(max_seq)
    if stand_in is None:
        _, cfg, _ = _family_config(config)
        stand_in = ProgramBlocks(cfg, canonical, max_seq,
                                 int(eng.get("prefill_chunk", 256)),
                                 eng.get("kv_cache_dtype", "bf16"))
    ids = check_ids(seed, int(arch["vocab"]), n_prefill + DECODE_ROWS)
    out = layer_errors(stand_in, canonical, arch, quant, ids, n_prefill)
    out["limits"] = reference.layer_limits(config)
    out["within"] = _within(out["found"], out["limits"])
    out["seconds"] = time.monotonic() - t_start
    return out


def main(argv=None) -> int:
    """The sound program, then the controls (module docstring)."""
    import argparse
    import json

    here = Path(__file__).resolve().parent.parent
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--skip-sound", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    from harness import common, spec, weights_afmoe as weights

    config = json.loads(
        (here / "configs" / f"{args.config}.json").read_text())
    if args.tiny:
        config = spec.deep_update(config, config["tiny"])
    arch = config["reference"]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    canonical = weights.canonical_params(config, args.seed, check=False)
    sound = None
    if not args.skip_sound:
        check = layer_check(config, canonical, args.seed)
        common.print_compared(report(check))
        sound = check["within"]
        print(json.dumps({"control": None, "seed": args.seed,
                          "found": check["found"],
                          "limits": check["limits"],
                          "checked_layers": check["checked_layers"],
                          "seconds": check["seconds"],
                          "within": check["within"]}), flush=True)
    refused = {}
    for name in [c for c in args.controls.split(",") if c]:
        check = layer_check(config, canonical, args.seed,
                            stand_in=AlteredReference(arch, quant, canonical,
                                                      CONTROLS[name]))
        over = sorted(k for k, v in check["limits"].items()
                      if not _within(check["found"], {k: v}))
        refused[name] = not check["within"]
        print(json.dumps({"control": name, "seed": args.seed,
                          "found": check["found"], "over": over,
                          "seconds": check["seconds"],
                          "within": check["within"]}), flush=True)
    print(json.dumps({"seed": args.seed, "sound_within": sound,
                      "controls_refused": refused,
                      "correct": all(refused.values())
                      and sound is not False}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
