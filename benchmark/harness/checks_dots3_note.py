"""dots3-note held to its reference one block at a time, on the SAME
input, where neither of its coins can fall.

Why. The model makes two discontinuous choices a token: the router's
top 8 of 256 and, past ``index_topk`` positions, the indexer's top 2048.
Through 14 layers the program's hidden state drifts a few per cent from
the float32 reference's (bfloat16 rounding), and some token in most
sequences has its eighth and ninth expert, or its 2048th and 2049th
position, closer than that: from there on the two sides are different
functions of the token (``reference_dots3_note.tolerance``). The
end-to-end comparisons of the harness therefore hold garbage off and
cannot see a precision, a factor, a window one short or a selection
that is not the top. This check can: each block of each layer gets the
reference's own input, rounded to bfloat16 so that both sides read the
same numbers, and its output is held to the reference's for that input.

Which layers (PR 38; all 14 before, four to five windows' time after
every window). One of every KIND the cut holds, ``checked_layers``: the
leading layers up to the end of the first whole period, that is the
dense layer 0, layer 1 (full attention, experts), the window layers
with experts that follow and the full layer with its indexer and
experts that closes the period: layers 0-5 of the 14. The later periods
repeat these kinds shape for shape (one leaf a layer; the jitted
programs are one per kind and serve every layer of it), on other seeded
weights of the same distribution; the reference's stream is carried as
far as the last layer checked and no further.

What runs, at sizes where the mechanisms bind. A seeded sequence of
``PREFILL_ROWS + DECODE_ROWS`` tokens (4,096 + 8: twice ``index_topk``,
eight windows; ``prefill_rows`` is held above ``index_topk`` plus one
chunk, so that the selection drops positions inside a chunk and in
decode, and above the ring, so that it wraps: the smallest whole number
of 1024-row chunks that does is 4) walks the reference; at every layer
checked

- attention: the program's ``attention_block`` prefills the first rows
  in the cell's chunks (``engine.prefill_chunk``) into a NEW one-layer
  private cache of the cell's length (the expanded form over the live
  key blocks, the selection per row; a window layer's rows in position
  order), splices it into a one-slot slab as ``engine_insert`` does (the
  last 640 positions into the ring, mid-ring) and then takes the last
  rows one at a time through the slab with per-slot positions as the
  engine's (``dsa_index_score``, the selection,
  ``sparse_mla_decode``; ``window_mla_decode`` over the ring, the
  append kernels);
- a full layer also gives its index scores and its selection
  (``index_score_rel_l2`` over the live entries; ``index_overlap_min``,
  the smallest share over the rows of the reference's selected positions
  that the program selected too), and runs a second time with the
  REFERENCE's selection handed in (``given_selection_*``), which holds
  the attention's precision apart from the selection's coin: with its
  own selection a row whose 2048th score is nearly tied attends one
  other position than the reference's row, and that is no error;
- feed-forward: the program's ``moe_block`` (sigmoid router, the bias in
  the choice, ``routed_experts`` on the stacks where they lie, the
  shared expert) on the first rows as chunks and on the last rows as a
  batch of one-token slots; ``swiglu`` for the dense layer.

Compared: each reading against ``reference_dots3_note.layer_limits``,
the LARGEST over the layers checked (the smallest for the overlap). ``stand_in``
puts something else in the program's place through the same comparison:
the reference with a planted fault or a lower precision (``CONTROLS``).

As a command (``python3 benchmark/harness/checks_dots3_note.py --config
<name> --seed n [--controls a,b] [--tiny]``) it runs the sound program
and then each control; each prints one line, and the last line says
whether every control came out NOT within the limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

PREFILL_ROWS = 4096
DECODE_ROWS = 8
FULL, WINDOW = "full_attention", "sliding_attention"
KINDS = {FULL: "full_attention", WINDOW: "window_attention"}
# name -> the reference's ``alter``: each must come out not within
CONTROLS = {
    "first_2048": {"select": "first"},
    "window_512": {"window": -1},
    "no_gate": {"gate": False},
    "no_router_bias": {"router_bias": False},
    "no_rescale": {"rescale": False},
    "latent_fp8_e5m2": {"latent_dtype": "float8_e5m2"},
}


def prefill_rows(max_seq: int) -> int:
    return min(PREFILL_ROWS, max_seq // 2)


def checked_layers(arch: Dict[str, Any]) -> range:
    """The layers the check covers: from layer 0 to the end of the first
    whole period, the first full-attention layer that follows a window
    layer (every layer where no window layer is followed by one). They
    hold every kind of ``reference.layer_stack``: attention full and
    window, feed-forward dense and routed."""
    types = list(arch["layer_types"])[:int(arch["layers"])]
    for i in range(1, len(types)):
        if types[i] == FULL and types[i - 1] == WINDOW:
            return range(i + 1)
    return range(len(types))


def check_ids(seed: int, vocab: int, n: int):
    import numpy as np

    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 33]
                                 ).integers(1, vocab, n)


class ProgramBlocks:
    """The program's blocks of one layer at a time, on the canonical
    tree: ``cfg`` the family's config, ``max_seq`` the slab's, ``chunk``
    the engine's prefill chunk."""

    def __init__(self, cfg, canonical: Dict[str, Any], max_seq: int,
                 chunk: int, kv: str = "bf16"):
        import jax

        from bigdl_tpu.models import dots3_note as prog

        self.prog, self.cfg, self.canonical = prog, cfg, canonical
        self.max_seq, self.chunk, self.kv = max_seq, chunk, kv
        self.experts = canonical.get("experts")

        def attn(lp, y, cache, kind, selected):
            probe = {}
            out, cache = prog.attention_block(y, lp, cfg, cache, kind,
                                              selected, probe)
            return out, cache, probe

        self._attn = jax.jit(attn, static_argnums=3)
        self._moe = jax.jit(
            lambda lp, experts, i, h: prog.moe_block(h, lp, experts, i,
                                                     cfg)[0])
        self._dense = jax.jit(lambda lp, h: prog.swiglu(
            h, lp["gate_proj"], lp["up_proj"], lp["down_proj"]))
        self._at = (None, None)

    def _layer(self, i: int):
        if self._at[0] != i:
            self._at = (i, self.prog.prepare_layer(
                dict(self.canonical["layers"][i]), self.cfg.kind(i)))
        return self._at[1]

    def _pieces(self, n: int):
        """``(start, stop)`` of the chunks and then the decoded rows."""
        p = prefill_rows(self.max_seq)
        return ([(a, min(a + self.chunk, p))
                 for a in range(0, p, self.chunk)]
                + [(t, t + 1) for t in range(p, n)])

    def attention(self, i: int, kind: str, y, given=None):
        """Outputs ``[S, D]`` of the rows of ``y``; for a full layer also
        the index scores and the selection of every row (``[S, max_seq]``
        as numpy, by chunk). ``given`` ``[S, S]`` bool: the selection to
        attend."""
        import jax.numpy as jnp
        import numpy as np

        from bigdl_tpu.ops.kvcache import init_cache_spec

        lp = self._layer(i)
        one = dataclasses.replace(self.cfg, num_hidden_layers=1,
                                  layer_types=(kind,),
                                  first_k_dense_replace=1)
        # as the engine: chunks into a private cache that keeps a window
        # layer's rows in position order, the splice into a one-slot
        # slab (the ring), the decoded rows through the slab
        spec = self.prog.cache_spec(one)
        cache = init_cache_spec(spec.unrolled(), 1, self.max_seq,
                                kv_cache_dtype=self.kv)
        p = prefill_rows(self.max_seq)
        rows, scores, picked = [], [], []
        n = y.shape[0]
        for a, b in self._pieces(n):
            if a == p:
                cache = init_cache_spec(
                    spec, 1, self.max_seq, kv_cache_dtype=self.kv,
                    per_slot_pos=True).spliced(cache, 0, p)
            sel = None
            if given is not None:
                sel = jnp.zeros((1, b - a, self.max_seq), bool).at[
                    0, :, :n].set(given[a:b])
            out, cache, probe = self._attn(lp, y[None, a:b], cache, kind,
                                           sel)
            rows.append(np.asarray(out[0], np.float32))
            if probe and given is None:     # a given selection is not read
                scores.append(np.asarray(probe["index_scores"][0])[:, :n])
                picked.append(np.asarray(probe["selected"][0])[:, :n])
        got = {"out": np.concatenate(rows)}
        if scores:
            got["index_scores"] = np.concatenate(scores)
            got["selected"] = np.concatenate(picked)
        return got

    def feed_forward(self, i: int, h):
        import numpy as np

        lp, p = self._layer(i), prefill_rows(self.max_seq)
        if i < self.cfg.n_dense:
            run = lambda x: self._dense(lp, x)                 # noqa: E731
        else:
            run = lambda x: self._moe(lp, self.experts,        # noqa: E731
                                      i - self.cfg.n_dense, x)
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

        from harness import reference_dots3_note as reference

        alter = dict(alter)
        if alter.get("window") == -1:        # one position short
            alter["window"] = int(arch["window"]["window"]) - 1
        if isinstance(alter.get("latent_dtype"), str):
            alter["latent_dtype"] = jnp.dtype(alter["latent_dtype"])
        self.reference, self.canonical = reference, canonical
        self.arch, self.quant, self.alter = arch, quant, alter

        def attn(y, lp, kind, given):
            probe = {}
            out = reference.attention(y, lp, arch, quant, kind, alter,
                                      given, probe)
            return out, probe

        self._attn = jax.jit(attn, static_argnums=2)
        self._ff = jax.jit(lambda h, lp, ex: reference.feed_forward(
            h, lp, ex, arch, quant, alter))

    def attention(self, i: int, kind: str, y, given=None):
        import jax
        import jax.numpy as jnp
        import numpy as np

        with jax.default_matmul_precision("highest"):
            out, probe = self._attn(y.astype(jnp.float32),
                                    self.canonical["layers"][i], kind, given)
        got = {"out": np.asarray(out)}
        got.update({k: np.asarray(v) for k, v in probe.items()})
        return got

    def feed_forward(self, i: int, h):
        import jax
        import jax.numpy as jnp
        import numpy as np

        n_dense = int(self.arch["first_k_dense"])
        ex = None
        if i >= n_dense:
            ex = jax.tree.map(lambda a: a[i - n_dense],
                              self.canonical["experts"])
        with jax.default_matmul_precision("highest"):
            return np.asarray(self._ff(h.astype(jnp.float32),
                                       self.canonical["layers"][i], ex))


def layer_errors(blocks, canonical: Dict[str, Any], arch: Dict[str, Any],
                 quant: Dict[str, Any], ids, n_prefill: int
                 ) -> Dict[str, Any]:
    """``blocks`` against the reference's blocks on the same inputs, in
    ``checked_layers``: for each reading the largest over those layers
    (``index_overlap_min``: the smallest), and every layer's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import reference_dots3_note as reference

    eps = float(arch["norm_eps"])
    norm = jax.jit(lambda x, w: reference._rms_norm(x, w, eps).astype(
        jnp.bfloat16))

    def ref_attn(y, lp, kind):
        probe = {}
        out = reference.attention(y, lp, arch, quant, kind, probe=probe)
        return out, probe

    attn = jax.jit(ref_attn, static_argnums=2)
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

    x = canonical["embed_tokens"][jnp.asarray(list(ids), jnp.int32)].astype(
        jnp.float32)
    covered = checked_layers(arch)
    for i, kind, lp, ex in reference.layer_stack(canonical, arch):
        if i not in covered:
            break        # the stream goes no further than the check
        y = ref(norm, x, lp["input_layernorm"])
        a, probe = ref(attn, y.astype(jnp.float32), lp, kind)
        got = blocks.attention(i, kind, y)
        hold(KINDS[kind], got["out"], a)
        if kind == FULL:
            want_s = np.asarray(probe["index_scores"])
            want_sel = np.asarray(probe["selected"])
            live = np.isfinite(want_s)
            gs = np.where(live, got["index_scores"], 0.0)
            per_layer.setdefault("index_score_rel_l2", []).append(
                reference.relative_l2(gs, np.where(live, want_s, 0.0)))
            both = (got["selected"] & want_sel).sum(axis=1)
            per_layer.setdefault("index_overlap_min", []).append(
                float((both / np.maximum(want_sel.sum(axis=1), 1)).min()))
            again = blocks.attention(i, kind, y, given=probe["selected"])
            hold("given_selection", again["out"], a)
        x = x + a
        h = ref(norm, x, lp["post_attention_layernorm"])
        f = ref(ff, h.astype(jnp.float32), lp, ex)
        hold("ffn", blocks.feed_forward(i, h), f)
        x = x + f
    found = {k: (min(v) if k == "index_overlap_min" else max(v))
             for k, v in per_layer.items()}
    return {"found": found, "layers": per_layer,
            "checked_layers": list(covered)}


def _within(found, limits) -> bool:
    return all(k in found and (found[k] >= v if k == "index_overlap_min"
                               else found[k] <= v)
               for k, v in limits.items())


def layer_check(config: Dict[str, Any], canonical: Dict[str, Any],
                seed: int, stand_in=None) -> Dict[str, Any]:
    """The check of ``config`` on the canonical tree of ``seed``: the
    program's blocks (or ``stand_in``) against the reference's, with the
    limits and the verdict."""
    import time

    from harness import reference_dots3_note as reference
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


def report(check: Dict[str, Any]) -> list:
    """A note line with every checked layer's reading; returns each
    compared number beside its limit, ``(name, value, limit[,
    "floor"])``, for ``common.print_compared`` or the runner's last
    lines."""
    from harness import common

    common.note(info="layer_check", found=check["found"],
                limits=check["limits"], within=check["within"],
                checked_layers=check["checked_layers"],
                seconds=check["seconds"], layers=check["layers"])
    return [(k, check["found"].get(k), limit, "floor")
            if k == "index_overlap_min" else
            (k if k.startswith("index_") else f"layer_rel_l2.{k}",
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

    from harness import common, spec, weights_dots3_note as weights

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
