"""DeepSeek-V2 held to its reference one block at a time, on the SAME
input, where the router's coin cannot fall.

Why. Routing is discontinuous. Through 20 layers the program's hidden
state drifts a few per cent from the float32 reference's (bfloat16
rounding, as in the dense configurations), and at the published
initializer_range the router's sixth and seventh score, or its third and
fourth group, lie closer than that for some token in most sequences:
program and reference then send the token to different experts and its
logits part by tens of per cent (``reference_deepseek_v2.tolerance``).
The end-to-end comparisons of the harness therefore hold garbage off
(a wrong row, page or position) and cannot see a precision or a factor.
This check can: each block of each layer gets the reference's own input,
rounded to bfloat16 so that both sides read the same numbers (router
logits then agree to float32 rounding and no expert is swapped), and its
output is held to the reference's output for that input.

What runs. A seeded sequence of ``PREFILL_ROWS + DECODE_ROWS`` tokens
walks the reference; at every layer

- attention: the program's ``mla_attention`` prefills the first rows
  in one chunk into a NEW one-layer latent slab of the cell's length
  and type (expanded K and V) and then takes the last rows one at a
  time through it (absorbed, ``mla_decode_attention``,
  ``mla_latent_append``), per-slot positions as the engine's;
- feed-forward: the program's ``moe_block`` (router, ``routed_experts``
  on the layer stacks where they lie, shared experts) on the first rows
  as one chunk (``moe_routed_prefill``) and on the last rows as a batch
  of one-token slots (``moe_routed_decode``); ``swiglu`` for a dense
  layer.

Compared: the relative L2 of each of the four outputs against the
reference's, the LARGEST over the layers; limits and their readings:
``reference_deepseek_v2.layer_limits``. ``stand_in`` puts something else
in the program's place through the same comparison: the reference with
its latent rows in a lower precision (the control), or a program with a
planted fault (tests).

As a command (``python3 benchmark/harness/checks_deepseek_v2.py --config
<name> --seed n [--latent-dtype float8_e5m2] [--tiny]``) it runs the
control the way ``serve_runner`` runs the check: the stand-in's logits and blocks through
``serve_runner.logits_errors`` and ``layer_errors`` against their limits;
the last line says whether it came out ``correct``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

PREFILL_ROWS = 256
DECODE_ROWS = 8
EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")
NAMES = ("attention_prefill", "attention_decode", "ffn_prefill",
         "ffn_decode")


def prefill_rows(max_seq: int) -> int:
    """A chunk as the engine prefills it, or half a short slab."""
    return min(PREFILL_ROWS, max_seq // 2)


def check_ids(seed: int, vocab: int, n: int):
    import numpy as np

    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 29]
                                 ).integers(1, vocab, n)


class ProgramBlocks:
    """The program's blocks of one layer at a time, on the canonical
    tree: ``cfg`` the family's config, ``max_seq`` and ``kv`` the
    slab's."""

    def __init__(self, cfg, canonical: Dict[str, Any], max_seq: int,
                 kv: str = "bf16"):
        import jax

        from bigdl_tpu.models import deepseek_v2 as prog

        self.prog, self.cfg, self.canonical = prog, cfg, canonical
        self.max_seq, self.kv = max_seq, kv
        self.one = dataclasses.replace(cfg, num_hidden_layers=1)
        self.experts = {k: canonical["moe_layers"][k] for k in EXPERT_KEYS
                        } if "moe_layers" in canonical else None
        self._attn = jax.jit(
            lambda lp, y, cache: prog.mla_attention(y, lp, cfg, cache))
        self._moe = jax.jit(
            lambda lp, experts, i, h: prog.moe_block(h, lp, experts, i,
                                                     cfg)[0])
        self._dense = jax.jit(lambda lp, h: prog.swiglu(
            h, lp["gate_proj"], lp["up_proj"], lp["down_proj"]))
        self._at = (None, None)

    def _layer(self, group: str, i: int):
        """Layer ``i`` of ``group`` as ``forward`` serves it."""
        import jax

        if self._at[0] != (group, i):
            one = {k: jax.tree.map(lambda a: a[i:i + 1], v)
                   for k, v in self.canonical[group].items()
                   if k not in EXPERT_KEYS}
            served = self.prog.prepare_params({group: one}, self.cfg)[group]
            self._at = ((group, i), jax.tree.map(lambda a: a[0], served))
        return self._at[1]

    def attention(self, group: str, i: int, y):
        import jax.numpy as jnp
        import numpy as np

        lp, p = self._layer(group, i), prefill_rows(self.max_seq)
        cache = self.prog.new_cache(self.one, 1, self.max_seq, self.kv)
        cache = cache.reset_pos(jnp.zeros((1,), jnp.int32))
        out, cache = self._attn(lp, y[None, :p], cache)
        rows = [np.asarray(out[0], np.float32)]
        for t in range(p, y.shape[0]):
            out, cache = self._attn(lp, y[None, t:t + 1], cache)
            rows.append(np.asarray(out[0], np.float32))
        return np.concatenate(rows)

    def feed_forward(self, group: str, i: int, h):
        import numpy as np

        lp, p = self._layer(group, i), prefill_rows(self.max_seq)
        if group == "dense_layers":
            run = lambda x: self._dense(lp, x)                 # noqa: E731
        else:
            run = lambda x: self._moe(lp, self.experts, i, x)  # noqa: E731
        return np.concatenate([
            np.asarray(run(h[None, :p])[0], np.float32),
            np.asarray(run(h[p:, None])[:, 0], np.float32)])


class LowerPrecisionBlocks:
    """The control: the reference itself with the rows a latent cache
    holds rounded to ``latent_dtype``."""

    def __init__(self, arch, quant, canonical, latent_dtype):
        import jax

        from harness import reference_deepseek_v2 as reference

        self.canonical = canonical
        self._attn = jax.jit(lambda y, lp: reference.attention(
            y, lp, arch, quant, latent_dtype))
        self._ff = jax.jit(lambda h, lp: reference.feed_forward(
            h, lp, arch, quant))

    def _run(self, fn, group, i, x):
        import jax
        import jax.numpy as jnp
        import numpy as np

        with jax.default_matmul_precision("highest"):
            return np.asarray(fn(
                x.astype(jnp.float32),
                jax.tree.map(lambda a: a[i], self.canonical[group])))

    def attention(self, group, i, y):
        return self._run(self._attn, group, i, y)

    def feed_forward(self, group, i, h):
        return self._run(self._ff, group, i, h)


def layer_errors(blocks, canonical: Dict[str, Any], arch: Dict[str, Any],
                 quant: Dict[str, Any], ids, n_prefill: int
                 ) -> Dict[str, Any]:
    """``blocks`` (``attention`` / ``feed_forward`` of ``(group, index,
    bfloat16 input [S, D])`` -> float32 ``[S, D]``) against the
    reference's blocks on the same inputs: for each of ``NAMES`` the
    largest relative L2 over the layers (``found``), and every layer's
    (``layers``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import reference_deepseek_v2 as reference

    eps = float(arch["norm_eps"])
    norm = jax.jit(lambda x, w: reference._rms_norm(x, w, eps).astype(
        jnp.bfloat16))
    attn = jax.jit(lambda y, lp: reference.attention(y, lp, arch, quant))
    ff = jax.jit(lambda h, lp: reference.feed_forward(h, lp, arch, quant))

    def ref(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    per_layer = {k: [] for k in NAMES}

    def hold(kind, got, want):
        want = np.asarray(want)
        per_layer[f"{kind}_prefill"].append(
            reference.relative_l2(got[:n_prefill], want[:n_prefill]))
        per_layer[f"{kind}_decode"].append(
            reference.relative_l2(got[n_prefill:], want[n_prefill:]))

    x = canonical["embed_tokens"][jnp.asarray(list(ids), jnp.int32)].astype(
        jnp.float32)
    for group, i, lp in reference.layer_stack(canonical):
        y = ref(norm, x, lp["input_layernorm"])
        a = ref(attn, y.astype(jnp.float32), lp)
        hold("attention", blocks.attention(group, i, y), a)
        x = x + a
        h = ref(norm, x, lp["post_attention_layernorm"])
        f = ref(ff, h.astype(jnp.float32), lp)
        hold("ffn", blocks.feed_forward(group, i, h), f)
        x = x + f
    return {"found": {k: max(v) for k, v in per_layer.items()},
            "layers": per_layer}


def layer_check(config: Dict[str, Any], canonical: Dict[str, Any],
                seed: int, stand_in=None) -> Dict[str, Any]:
    """The check of ``config`` on the canonical tree of ``seed``: the
    program's blocks (or ``stand_in``) against the reference's, with the
    limits and the verdict."""
    import time

    from harness import reference_deepseek_v2 as reference
    from harness.weights import _family_config

    t_start = time.monotonic()
    arch, eng = config["reference"], config["engine"]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    max_seq = int(eng["max_seq"])
    n_prefill = prefill_rows(max_seq)
    if stand_in is None:
        _, cfg, _ = _family_config(config)
        stand_in = ProgramBlocks(cfg, canonical, max_seq,
                                 eng.get("kv_cache_dtype", "bf16"))
    ids = check_ids(seed, int(arch["vocab"]), n_prefill + DECODE_ROWS)
    out = layer_errors(stand_in, canonical, arch, quant, ids, n_prefill)
    out["limits"] = reference.layer_limits(config)
    out["within"] = all(out["found"][k] <= v
                        for k, v in out["limits"].items())
    out["seconds"] = time.monotonic() - t_start
    return out


def report(check: Dict[str, Any]) -> list:
    """A note line with every layer's reading; returns each compared
    number beside its limit, ``(name, value, limit)``, for
    ``common.print_compared`` or the runner's last lines."""
    from harness import common

    common.note(info="layer_check", found=check["found"],
                limits=check["limits"], within=check["within"],
                seconds=check["seconds"], layers=check["layers"])
    return [(f"layer_rel_l2.{k}", check["found"][k], limit)
            for k, limit in check["limits"].items()]


def main(argv=None) -> int:
    """The control through the harness's comparisons (module docstring)."""
    import argparse
    import json
    import sys
    from pathlib import Path

    here = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(here), str(here.parent)]
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--latent-dtype", default="float8_e5m2")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax.numpy as jnp
    import numpy as np

    from harness import (common, reference_deepseek_v2 as reference,
                         serve_runner, spec, weights_deepseek_v2 as weights)

    config = json.loads(
        (here / "configs" / f"{args.config}.json").read_text())
    if args.tiny:
        config = spec.deep_update(config, config["tiny"])
    arch = config["reference"]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    kv = config["engine"].get("kv_cache_dtype", "bf16")
    lower = jnp.dtype(args.latent_dtype)
    canonical = weights.canonical_params(config, args.seed, check=False)
    ids = serve_runner.check_ids(args.seed, int(arch["vocab"]))
    n_prompt = serve_runner.REF_PROMPT_TOKENS
    rows = np.asarray(reference.all_logits(
        canonical, arch, quant, [int(x) for x in ids], first=n_prompt - 1,
        latent_dtype=lower))
    rel = serve_runner.logits_errors(reference, canonical, arch, quant, ids,
                                     rows[0], rows[1:])
    tol = reference.tolerance(config, kv)
    check = layer_check(
        config, canonical, args.seed,
        stand_in=LowerPrecisionBlocks(arch, quant, canonical, lower))
    verdicts = {"reference_within_tolerance": max(rel.values()) <= tol,
                "layers_within_limits": check["within"]}
    common.report_compared(
        report(check)
        + [(f"reference_rel_l2.{k}", v, tol) for k, v in rel.items()],
        verdicts)
    print(json.dumps({"control": args.latent_dtype, "seed": args.seed,
                      "reference_rel_l2": rel, "reference_tolerance": tol,
                      "layer_rel_l2": check["found"],
                      "layer_limits": check["limits"],
                      "correct": all(verdicts.values())}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
