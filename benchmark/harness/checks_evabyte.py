"""EvaByte held to its reference one block at a time, on the SAME input,
at rows where its mechanisms bind.

Why. The harness's own logits comparison walks 32 + 8 positions
(``serve_runner.REF_PROMPT_TOKENS``), which never leave the first window:
there the model is plain causal attention and neither a summary nor a
refilled window plane is read. What the window served is compared token
by token (``served.compare``), which tells a wrong row from a right one
and no precision. This check can: each block gets the reference's own
input, rounded to bfloat16 so that both sides read the same numbers, and
its output is held to the reference's for that input.

Which layers. ONE (there is one kind) and the head.

What runs. A seeded sequence of ``prefill_rows + decode_rows`` tokens
(three windows less half a chunk, then two chunks: 6,136 + 32 at the
published sizes) walks the reference's layer 0.

- attention: the program's ``attention_block`` prefills the first rows
  in the cell's chunks (``engine.prefill_chunk``; the last one padded
  with zero rows as the engine pads a prompt) into a NEW one-layer
  private cache of the slab's geometry, splices it into a one-slot slab
  as ``engine_insert`` does, and takes the last rows one at a time
  through the slab at per-slot positions (the row append,
  ``eva_summarize``, ``eva_decode_attention``). The decoded rows cross a
  window boundary (the exact set falls to one key over every summary so
  far) and complete the chunk after it, whose summary row AS THE DECODE
  STEP WROTE IT is read back from the plane with those the prefill
  wrote: ``eva_summary_rel_l2``, the larger of the keys' and the
  values' over every complete chunk.
- feed-forward: the program's ``swiglu`` on the first rows as chunks and
  on the last rows as a batch of one-token slots.
- head: the program's final norm and head on the last rows, all
  ``pred_heads * vocab`` columns.

Compared: each reading against ``reference_evabyte.layer_limits``.
``stand_in`` puts something else in the program's place through the same
comparison: the reference with a planted fault or a lower precision
(``CONTROLS``).

As a command (``python3 benchmark/harness/checks_evabyte.py --config
<name> --seed n [--controls a,b] [--tiny]``) it runs the sound program
and then each control; each prints one line, and the last line says
whether every control came out NOT within the limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

# name -> the reference's ``alter``: each must come out not within
CONTROLS = {
    "no_summaries": {"summaries": False},
    "sliding_window": {"sliding": True},
    "no_mu": {"mu": False},
    "kv_fp8_e5m2": {"kv_dtype": "float8_e5m2"},
}


def prefill_rows(arch: Dict[str, Any]) -> int:
    return 3 * int(arch["window"]) - int(arch["chunk"]) // 2


def decode_rows(arch: Dict[str, Any]) -> int:
    return 2 * int(arch["chunk"])


def check_ids(seed: int, vocab: int, n: int):
    import numpy as np

    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 39]
                                 ).integers(0, vocab, n)


class ProgramBlocks:
    """The program's blocks of layer 0 on the canonical tree: ``cfg``
    the family's config, ``max_seq`` the slab's, ``chunk`` the engine's
    prefill chunk."""

    def __init__(self, cfg, canonical: Dict[str, Any], max_seq: int,
                 chunk: int, n_prefill: int, kv: str = "bf16"):
        import jax

        from bigdl_tpu.models import evabyte as prog

        self.prog, self.max_seq, self.chunk, self.kv = (prog, max_seq,
                                                        chunk, kv)
        self.n_prefill = n_prefill
        self.cfg = dataclasses.replace(cfg, num_hidden_layers=1)
        self.lp = prog.prepare_layer(dict(canonical["layers"][0]))
        self.norm, self.lm_head = canonical["norm"], canonical["lm_head"]
        one = self.cfg

        def attn(lp, y, cache):
            cos, sin = prog.rope_table(one, cache.pos, y.shape[1])
            out, cache = prog.attention_block(y, lp, one, cache, 0, cos,
                                              sin)
            return out, cache.replace(pos=cache.pos + y.shape[1])

        self._attn = jax.jit(attn, donate_argnums=2)
        self._ffn = jax.jit(lambda lp, h: prog.swiglu(h, lp))
        self._head = jax.jit(lambda x, norm, w: prog.linear(
            prog._norm(x, norm, one.rms_norm_eps), w))

    def attention(self, y):
        """``{"out": [S, D], "k_sum", "v_sum": [S // c, H, hd]}`` of the
        rows of ``y`` (bfloat16): as the engine, chunks into a private
        cache, the splice into a one-slot slab, the decoded rows through
        the slab."""
        import jax.numpy as jnp
        import numpy as np

        from bigdl_tpu.ops.kvcache import init_cache_spec

        spec = self.prog.cache_spec(self.cfg)
        p, chunk, n = self.n_prefill, self.chunk, y.shape[0]
        alloc = -(-p // chunk) * chunk
        cache = init_cache_spec(spec, 1, alloc, kv_cache_dtype=self.kv)
        rows = []
        for a in range(0, p, chunk):
            part = y[a:min(a + chunk, p)]
            pad = chunk - part.shape[0]
            if pad:                     # the engine pads the last chunk
                part = jnp.concatenate(
                    [part, jnp.zeros((pad, y.shape[1]), y.dtype)])
            out, cache = self._attn(self.lp, part[None], cache)
            rows.append(np.asarray(out[0, :chunk - pad], np.float32))
        cache = init_cache_spec(
            spec, 1, self.max_seq, kv_cache_dtype=self.kv,
            per_slot_pos=True).spliced(cache, 0, p)
        for t in range(p, n):
            out, cache = self._attn(self.lp, y[None, t:t + 1], cache)
            rows.append(np.asarray(out[0], np.float32))
        done = n // self.cfg.chunk_size
        return {"out": np.concatenate(rows),
                "k_sum": np.asarray(cache.sum_k[0, 0, :done], np.float32),
                "v_sum": np.asarray(cache.sum_v[0, 0, :done], np.float32)}

    def feed_forward(self, h):
        import numpy as np

        p = self.n_prefill
        parts = [np.asarray(self._ffn(self.lp, h[None, a:min(
            a + self.chunk, p)])[0], np.float32)
            for a in range(0, p, self.chunk)]
        parts.append(np.asarray(self._ffn(self.lp, h[p:, None])[:, 0],
                                np.float32))
        return np.concatenate(parts)

    def head(self, x):
        import numpy as np

        return np.asarray(self._head(x[:, None], self.norm,
                                     self.lm_head)[:, 0], np.float32)


class AlteredReference:
    """A control: the reference itself with ``alter`` (a planted fault
    or a precision below the configuration's) in the program's place."""

    def __init__(self, arch, quant, canonical, alter):
        import jax
        import jax.numpy as jnp

        from harness import reference_evabyte as reference

        alter = dict(alter)
        if isinstance(alter.get("kv_dtype"), str):
            alter["kv_dtype"] = jnp.dtype(alter["kv_dtype"])
        self.reference, self.canonical = reference, canonical
        self.arch, self.quant = arch, quant

        def attn(y, lp):
            probe = {}
            out = reference.attention(y, lp, arch, quant, alter, probe)
            return out, probe

        self._attn = jax.jit(attn)
        self._ffn = jax.jit(lambda h, lp: reference.feed_forward(h, lp,
                                                                 quant))
        self._head = jax.jit(lambda x, norm, w: reference.head(
            x, norm, w, arch, quant))

    def _run(self, fn, *args):
        import jax
        import numpy as np

        with jax.default_matmul_precision("highest"):
            return jax.tree.map(np.asarray, fn(*args))

    def attention(self, y):
        import jax.numpy as jnp

        out, probe = self._run(self._attn, y.astype(jnp.float32),
                               self.canonical["layers"][0])
        return dict(probe, out=out)

    def feed_forward(self, h):
        import jax.numpy as jnp

        return self._run(self._ffn, h.astype(jnp.float32),
                         self.canonical["layers"][0])

    def head(self, x):
        return self._run(self._head, x, self.canonical["norm"],
                         self.canonical["lm_head"])


def layer_errors(blocks, canonical: Dict[str, Any], arch: Dict[str, Any],
                 quant: Dict[str, Any], ids, n_prefill: int
                 ) -> Dict[str, Any]:
    """``blocks`` against the reference's blocks of layer 0 and its head
    on the same inputs."""
    import jax.numpy as jnp
    import numpy as np

    from harness import reference_evabyte as reference

    sound = AlteredReference(arch, quant, canonical, {})
    eps = float(arch["norm_eps"])
    lp = canonical["layers"][0]
    found: Dict[str, float] = {}
    detail: Dict[str, float] = {}

    def hold(kind, got, want):
        for part, sl in (("prefill", slice(None, n_prefill)),
                         ("decode", slice(n_prefill, None))):
            found[f"{kind}_{part}"] = reference.relative_l2(got[sl],
                                                            want[sl])

    x = canonical["embed_tokens"][jnp.asarray(list(ids), jnp.int32)].astype(
        jnp.float32)
    y = reference._rms_norm(x, lp["input_layernorm"], eps).astype(
        jnp.bfloat16)
    want = sound.attention(y)
    got = blocks.attention(y)
    hold("eva_attention", got["out"], want["out"])
    # chunks the prefill wrote whole, and those a decode step finished
    first_decoded = n_prefill // int(arch["chunk"])
    for name in ("k_sum", "v_sum"):
        for part, sl in (("prefill", slice(None, first_decoded)),
                         ("decode", slice(first_decoded, None))):
            detail[f"{name}_{part}"] = reference.relative_l2(
                got[name][sl], want[name][sl])
    found["eva_summary_rel_l2"] = max(detail.values())
    x = x + want["out"]
    h = reference._rms_norm(x, lp["post_attention_layernorm"], eps).astype(
        jnp.bfloat16)
    f = sound.feed_forward(h)
    hold("ffn", blocks.feed_forward(h), f)
    # the head on the decoded rows, both sides on the same rounded state
    last = jnp.asarray(np.asarray(x + f)[n_prefill:]).astype(
        jnp.bfloat16).astype(jnp.float32)
    found["head_rel_l2"] = reference.relative_l2(blocks.head(last),
                                                 sound.head(last))
    return {"found": found, "summaries": detail}


def _within(found, limits) -> bool:
    return all(k in found and found[k] <= v for k, v in limits.items())


def layer_check(config: Dict[str, Any], canonical: Dict[str, Any],
                seed: int, stand_in=None) -> Dict[str, Any]:
    """The check of ``config`` on the canonical tree of ``seed``: the
    program's blocks (or ``stand_in``) against the reference's, with the
    limits and the verdict."""
    import time

    from harness import reference_evabyte as reference
    from harness.weights import _family_config

    t_start = time.monotonic()
    arch, eng = config["reference"], config["engine"]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    n_prefill = prefill_rows(arch)
    if stand_in is None:
        _, cfg, _ = _family_config(config)
        stand_in = ProgramBlocks(cfg, canonical, int(eng["max_seq"]),
                                 int(eng.get("prefill_chunk", 256)),
                                 n_prefill,
                                 eng.get("kv_cache_dtype", "bf16"))
    ids = check_ids(seed, int(arch["vocab"]), n_prefill + decode_rows(arch))
    out = layer_errors(stand_in, canonical, arch, quant, ids, n_prefill)
    out["limits"] = reference.layer_limits(config)
    out["within"] = _within(out["found"], out["limits"])
    out["rows"] = {"prefill": n_prefill, "decode": decode_rows(arch)}
    out["seconds"] = time.monotonic() - t_start
    return out


def report(check: Dict[str, Any]) -> list:
    """A note line with every reading; returns each compared number
    beside its limit, ``(name, value, limit)``, for
    ``common.print_compared`` or the runner's last lines."""
    from harness import common

    common.note(info="layer_check", found=check["found"],
                limits=check["limits"], within=check["within"],
                rows=check["rows"], summaries=check["summaries"],
                seconds=check["seconds"])
    return [(k if k.endswith("rel_l2") else f"layer_rel_l2.{k}",
             check["found"].get(k), limit)
            for k, limit in check["limits"].items()]


def main(argv=None) -> int:
    """The sound program, then the controls (module docstring)."""
    import argparse
    import json
    import sys
    from pathlib import Path

    here = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(here), str(here.parent)]
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--skip-sound", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    from harness import common, spec, weights_evabyte as weights

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
                          "summaries": check["summaries"],
                          "limits": check["limits"],
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
