"""DeepSeek-V3.2 held to its reference one block at a time, on the SAME
input, where none of its coins can fall.

Why. As ``checks_dots3_note``: the model makes discontinuous choices a
token (the router's 4 of 8 groups and 8 of their experts; past
``index_topk`` positions the indexer's top 2048), the program's hidden
state drifts a few per cent from the float32 reference's through the
depth, and from the first choice that falls differently the two sides
are different functions of the token. The end-to-end comparisons hold
garbage off and cannot see a precision, a group rule, a swapped half of
``eh_proj`` or a verify step that reads or keeps the wrong row. This
check can: each block gets the reference's own input, rounded to
bfloat16 so that both sides read the same numbers.

Which blocks: each KIND once (``checked_bodies``): the dense body (layer
0), one expert body (layer 1), the MTP module (its combine, its block as
one more body, its head) and the main head.

What runs, at sizes where the mechanisms bind. A seeded sequence of
``PREFILL_ROWS + DECODE_ROWS`` tokens (4,096 + 8: twice ``index_topk``,
so the selection drops positions inside a chunk and in every decoded
row) walks the reference; at every body checked

- attention: the program's ``attention_block`` prefills the first rows
  in the cell's chunks into a NEW one-body private cache, splices it
  into a one-slot slab as ``engine_insert`` does and then takes
  ``ONE_ROWS`` rows one at a time (the one-row kernels) and the rest as
  VERIFY steps of two rows (``dsa_index_score`` and ``sparse_mla_decode``
  with the rows folded beside the heads, the append kernel twice);
  ``full_attention_*`` / ``index_*`` on its own selection,
  ``given_selection_*`` (the one-row decode rows) and ``verify_rel_l2``
  (the two-row steps) on the REFERENCE's selection;
  ``verify_live_mismatch`` counts index-score entries of the decoded
  rows that are live on one side only;
- ``accepted_stream``: from the same spliced slab, steps of two rows
  with FORCED outcomes (``OUTCOMES``): a rejected step's second row is a
  stranger's (another position's input) and ``pos`` goes on by one, so
  the next step has to overwrite it; an accepted step's goes on by two.
  What the planes hold afterwards at the decoded positions is held, row
  by row, to the rows the reference expects of the final stream;
- feed-forward: ``moe_block`` (the grouped biased choice,
  ``routed_experts`` on the stacks where they lie, the shared expert) on
  the first rows as chunks and on the last rows as a batch of slots of
  two rows; ``swiglu`` for the dense body;
- the MTP module's combine and the two heads on the reference's rows.

``stand_in`` puts something else in the program's place through the same
comparison: the reference with a planted fault or a lower precision
(``CONTROLS``). As a command (``python3 benchmark/harness/
checks_deepseek_v32.py --config <name> --seed n [--controls a,b]
[--tiny]``) it runs the sound program and then each control; each prints
one line, and the last line says whether every control came out NOT
within the limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

PREFILL_ROWS = 4096
DECODE_ROWS = 8
ONE_ROWS = 4          # decoded rows taken one at a time; the rest in twos
# forced outcomes of the accepted-stream steps: True keeps both rows
OUTCOMES = (False, True, False, False, True)
MTP = "mtp"
LOWER_IS_FLOOR = ("index_overlap_min",)
# name -> the reference's ``alter``: each must come out not within
CONTROLS = {
    "no_group_limit": {"group_limit": False},
    "group_score_max": {"group_score": "max"},
    "eh_proj_swapped": {"eh_swap": True},
    "no_hnorm": {"hnorm": False},
    "verify_row1_blind": {"blind": True},
    "dead_row_kept": {"keep_dead_row": True},
    "latent_fp8_e5m2": {"latent_dtype": "float8_e5m2"},
}


def prefill_rows(max_seq: int) -> int:
    return min(PREFILL_ROWS, max_seq // 2)


def checked_bodies(arch: Dict[str, Any]):
    """One body of each kind: the dense layer, one expert layer, the
    MTP module's block."""
    n_dense = int(arch["first_k_dense"])
    out = [0] if n_dense else []
    if int(arch["layers"]) > n_dense:
        out.append(n_dense)
    return out + ([MTP] if int(arch.get("mtp", 0)) else [])


def second_rows(n_prefill: int):
    """The decoded rows that are row 1 of a two-row step."""
    return tuple(range(n_prefill + ONE_ROWS + 1, n_prefill + DECODE_ROWS, 2))


def stranger(t: int, n_prefill: int) -> int:
    """The row whose input stands in for a rejected draft at row ``t``."""
    return (7 * t + 3) % n_prefill


def check_ids(seed: int, vocab: int, n: int):
    import numpy as np

    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 43]
                                 ).integers(1, vocab, n)


def _stack_index(canonical, i):
    """Where body ``i``'s routed experts lie in the stacks; None for a
    dense body."""
    n_dense = sum("router" not in x for x in canonical["layers"])
    if i == MTP:
        return len(canonical["layers"]) - n_dense
    return None if i < n_dense else i - n_dense


def _body(canonical, i):
    """``(leaves, routed experts or None)`` of body ``i``."""
    import jax

    lp = (canonical["mtp"]["block"] if i == MTP
          else canonical["layers"][i])
    j = _stack_index(canonical, i)
    if j is None:
        return lp, None
    return lp, jax.tree.map(lambda a: a[j], canonical["experts"])


class ProgramBlocks:
    """The program's blocks of one body at a time, on the canonical
    tree: ``cfg`` the family's config, ``max_seq`` the slab's, ``chunk``
    the engine's prefill chunk."""

    def __init__(self, cfg, canonical: Dict[str, Any], max_seq: int,
                 chunk: int, kv: str = "bf16"):
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.models import deepseek_v2, deepseek_v32 as prog
        from bigdl_tpu.ops.matmul import linear
        from bigdl_tpu.ops.norms import rms_norm

        self.prog, self.cfg, self.canonical = prog, cfg, canonical
        self.max_seq, self.chunk, self.kv = max_seq, chunk, kv
        self.one = dataclasses.replace(cfg, num_hidden_layers=1,
                                       num_nextn_predict_layers=0,
                                       first_k_dense_replace=1)
        one = self.one

        def attn(lp, y, cache, selected):
            probe = {}
            out, cache = prog.attention_block(y, lp, one, cache, selected,
                                              probe)
            return out, cache, probe

        self._attn = jax.jit(attn)
        self._moe = jax.jit(
            lambda lp, experts, j, h: deepseek_v2.moe_block(
                h, lp, experts, j, cfg)[0])
        self._dense = jax.jit(lambda lp, h: deepseek_v2.swiglu(
            h, lp["gate_proj"], lp["up_proj"], lp["down_proj"]))
        eps = cfg.rms_norm_eps

        def combine(m, emb, hidden, tokens):
            e = rms_norm(prog.embedding_lookup(emb, tokens, jnp.bfloat16),
                         m["enorm"], eps)
            return linear(jnp.concatenate(
                [e, rms_norm(hidden, m["hnorm"], eps)], axis=-1),
                m["eh_proj"])

        self._combine = jax.jit(combine)
        self._head = jax.jit(
            lambda x, norm, lm_head: linear(rms_norm(x, norm, eps), lm_head))
        self._at = (None, None)
        self._own = (None, None)

    def _layer(self, i):
        if self._at[0] != i:
            self._at = (i, self.prog.prepare_layer(
                dict(_body(self.canonical, i)[0]), self.cfg))
        return self._at[1]

    def _pieces(self, n: int):
        """``(start, stop)`` of the chunks, the one-row steps and the
        two-row steps."""
        p = prefill_rows(self.max_seq)
        return ([(a, min(a + self.chunk, p))
                 for a in range(0, p, self.chunk)]
                + [(t, t + 1) for t in range(p, p + ONE_ROWS)]
                + [(t, t + 2) for t in range(p + ONE_ROWS, n, 2)])

    def _prefilled(self, lp, y):
        """The one-slot slab after the chunks and the splice, and what
        the chunks gave."""
        from bigdl_tpu.ops.kvcache import init_cache_spec

        spec = self.prog.cache_spec(self.one)
        cache = init_cache_spec(spec, 1, self.max_seq,
                                kv_cache_dtype=self.kv)
        p = prefill_rows(self.max_seq)
        outs = []
        for a, b in self._pieces(p):
            if b > p:
                break
            outs.append((a, b) + self._attn(lp, y[None, a:b], cache,
                                            self._given(a, b)))
            cache = outs[-1][3]
        slab = init_cache_spec(spec, 1, self.max_seq, kv_cache_dtype=self.kv,
                               per_slot_pos=True).spliced(cache, 0, p)
        if self._sel is None:       # `stream` goes on from the same slab
            self._own = (id(lp), slab)
        return slab, outs

    def _given(self, a, b):
        import jax.numpy as jnp

        if self._sel is None:
            return None
        n = self._sel.shape[0]
        return jnp.zeros((1, b - a, self.max_seq), bool).at[
            0, :, :n].set(self._sel[a:b])

    def attention(self, i, y, given=None):
        """Outputs ``[S, D]`` of the rows of ``y``, the index scores and
        the selection of every row (``[S, S]`` as numpy). ``given`` ``[S,
        S]`` bool: the selection to attend."""
        import numpy as np

        lp = self._layer(i)
        self._sel = given
        n = y.shape[0]
        p = prefill_rows(self.max_seq)
        cache, outs = self._prefilled(lp, y)
        for a, b in self._pieces(n):
            if a < p:
                continue
            out = self._attn(lp, y[None, a:b], cache, self._given(a, b))
            cache = out[1]
            outs.append((a, b) + out)
        got = {"out": np.concatenate(
            [np.asarray(o[2][0], np.float32) for o in outs])}
        if given is None:
            got["index_scores"] = np.concatenate(
                [np.asarray(o[4]["index_scores"][0])[:, :n] for o in outs])
            got["selected"] = np.concatenate(
                [np.asarray(o[4]["selected"][0])[:, :n] for o in outs])
        return got

    def stream(self, i, y):
        """Forced accepts and rejects from the spliced slab: ``(latent
        rows, index rows)`` of the decoded positions as the planes hold
        them afterwards, and the slab's ``pos``."""
        import jax.numpy as jnp
        import numpy as np

        lp = self._layer(i)
        self._sel = None
        p = prefill_rows(self.max_seq)
        # the slab `attention` spliced for this body on its own selection
        cache = (self._own[1] if self._own[0] == id(lp)
                 else self._prefilled(lp, y)[0])
        pos = p
        for keep in OUTCOMES:
            second = y[pos + 1] if keep else y[stranger(pos + 1, p)]
            _, cache, _ = self._attn(
                lp, jnp.stack([y[pos], second])[None],
                cache.replace(pos=jnp.asarray([pos], jnp.int32)), None)
            pos += 2 if keep else 1
            cache = cache.replace(pos=jnp.asarray([pos], jnp.int32))
        rows = [np.asarray(plane[0, 0, :, p:pos], np.float32).T
                for plane in (cache.latent, cache.index)]
        return rows, int(np.asarray(cache.pos)[0])

    def feed_forward(self, i, h):
        import numpy as np

        lp, p = self._layer(i), prefill_rows(self.max_seq)
        j = _stack_index(self.canonical, i)
        if j is None:
            run = lambda x: self._dense(lp, x)                 # noqa: E731
        else:
            run = lambda x: self._moe(                         # noqa: E731
                lp, self.canonical["experts"], j, x)
        parts = [np.asarray(run(h[None, a:min(a + self.chunk, p)])[0],
                            np.float32) for a in range(0, p, self.chunk)]
        tail = h[p:].reshape(-1, 2, h.shape[-1])       # slots of two rows
        parts.append(np.asarray(run(tail), np.float32).reshape(
            -1, h.shape[-1]))
        return np.concatenate(parts)

    def combine(self, hidden, tokens):
        import numpy as np

        m = self.canonical["mtp"]
        return np.asarray(self._combine(
            m, self.canonical["embed_tokens"], hidden[None],
            tokens[None])[0], np.float32)

    def head(self, x, which: str):
        import numpy as np

        norm = (self.canonical["norm"] if which == "main"
                else self.canonical["mtp"]["shared_head_norm"])
        return np.asarray(self._head(x[None], norm,
                                     self.canonical["lm_head"])[0],
                          np.float32)


class AlteredReference:
    """A control: the reference itself with ``alter`` (a planted fault
    or a precision below the configuration's) in the program's place."""

    def __init__(self, arch, quant, canonical, alter, n_prefill: int):
        import jax
        import jax.numpy as jnp

        from harness import reference_deepseek_v32 as reference

        alter = dict(alter)
        if alter.pop("blind", False):
            alter["blind_rows"] = second_rows(n_prefill)
        if isinstance(alter.get("latent_dtype"), str):
            alter["latent_dtype"] = jnp.dtype(alter["latent_dtype"])
        self.reference, self.canonical = reference, canonical
        self.arch, self.quant, self.alter = arch, quant, alter
        self.n_prefill = n_prefill

        def attn(y, lp, given):
            probe = {}
            out = reference.attention(y, lp, arch, quant, alter, given, probe)
            return out, probe

        self._attn = jax.jit(attn)
        self._ff = jax.jit(lambda h, lp, ex: reference.feed_forward(
            h, lp, ex, arch, quant, alter))

    def _hi(self, fn, *args):
        import jax

        with jax.default_matmul_precision("highest"):
            return fn(*args)

    def attention(self, i, y, given=None):
        import jax.numpy as jnp
        import numpy as np

        out, probe = self._hi(self._attn, y.astype(jnp.float32),
                              _body(self.canonical, i)[0], given)
        got = {"out": np.asarray(out)}
        got.update({k: np.asarray(v) for k, v in probe.items()})
        return got

    def stream(self, i, y):
        return expected_stream(self.reference, self.canonical, self.arch,
                               self.quant, i, y, self.n_prefill,
                               keep_dead=self.alter.get("keep_dead_row",
                                                        False))

    def feed_forward(self, i, h):
        import jax.numpy as jnp
        import numpy as np

        lp, ex = _body(self.canonical, i)
        return np.asarray(self._hi(self._ff, h.astype(jnp.float32), lp, ex))

    def combine(self, hidden, tokens):
        import jax.numpy as jnp
        import numpy as np

        emb = self.canonical["embed_tokens"][tokens].astype(jnp.float32)
        return np.asarray(self._hi(
            self.reference.mtp_combine, self.canonical, self.arch, self.quant,
            hidden.astype(jnp.float32), emb, self.alter))

    def head(self, x, which: str):
        import jax.numpy as jnp
        import numpy as np

        norm = (self.canonical["norm"] if which == "main"
                else self.canonical["mtp"]["shared_head_norm"])
        return np.asarray(self.reference._head(
            self.canonical, self.arch, self.quant, x.astype(jnp.float32),
            norm))


def expected_stream(reference, canonical, arch, quant, i, y, n_prefill,
                    keep_dead: bool = False):
    """The rows the reference expects at the decoded positions after
    ``OUTCOMES`` and the ``pos`` they leave. ``keep_dead`` is a control:
    the stranger's row stays where a rejected step wrote it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lp = _body(canonical, i)[0]
    pos = n_prefill
    src = {}
    dead = {}
    for keep in OUTCOMES:
        src[pos] = pos
        if keep:
            src[pos + 1] = pos + 1
        elif keep_dead:
            dead[pos + 1] = stranger(pos + 1, n_prefill)
        pos += 2 if keep else 1
    src.update(dead)        # the control: the later write did not land
    at = list(range(n_prefill, pos))
    rows = jnp.stack([y[src[t]] for t in at]).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        lat, idx = jax.jit(
            lambda r, lp, positions: reference.cache_rows(
                r, lp, arch, quant, positions))(
            rows, lp, jnp.asarray(at, jnp.int32))
    return [np.asarray(lat), np.asarray(idx)], pos


def layer_errors(blocks, canonical: Dict[str, Any], arch: Dict[str, Any],
                 quant: Dict[str, Any], ids, n_prefill: int
                 ) -> Dict[str, Any]:
    """``blocks`` against the reference's blocks on the same inputs, in
    ``checked_bodies``: for each reading the largest over those bodies
    (``index_overlap_min``: the smallest), and every body's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import reference_deepseek_v32 as reference

    eps = float(arch["norm_eps"])
    norm = jax.jit(lambda x, w: reference._rms_norm(x, w, eps).astype(
        jnp.bfloat16))

    def ref_attn(y, lp):
        probe = {}
        out = reference.attention(y, lp, arch, quant, probe=probe)
        return out, probe

    attn = jax.jit(ref_attn)
    ff = jax.jit(lambda h, lp, ex: reference.feed_forward(h, lp, ex, arch,
                                                          quant))

    def ref(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    per: Dict[str, list] = {}
    one_rows = slice(n_prefill, n_prefill + ONE_ROWS)
    two_rows = slice(n_prefill + ONE_ROWS, None)
    rel = reference.relative_l2

    def hold(name, got, want, sl):
        per.setdefault(name, []).append(rel(got[sl], np.asarray(want)[sl]))

    ids = jnp.asarray(list(ids), jnp.int32)
    x = canonical["embed_tokens"][ids].astype(jnp.float32)
    bodies = checked_bodies(arch)
    for i in bodies:
        lp, ex = _body(canonical, i)
        if i == MTP:
            # the module's input: its combine of the stream so far (as
            # the hidden rows) and the NEXT tokens' embeddings
            nxt = jnp.roll(ids, -1)
            emb = canonical["embed_tokens"][nxt].astype(jnp.float32)
            hid = x.astype(jnp.bfloat16)
            want = ref(reference.mtp_combine, canonical, arch, quant,
                       hid.astype(jnp.float32), emb)
            got = blocks.combine(hid, nxt)
            per.setdefault("mtp_rel_l2", []).extend(
                [rel(got[:n_prefill], np.asarray(want)[:n_prefill]),
                 rel(got[n_prefill:], np.asarray(want)[n_prefill:])])
            x = want
        y = ref(norm, x, lp["input_layernorm"])
        a, probe = ref(attn, y.astype(jnp.float32), lp)
        got = blocks.attention(i, y)
        hold("full_attention_prefill", got["out"], a, slice(None, n_prefill))
        hold("full_attention_decode", got["out"], a,
             slice(n_prefill, None))
        want_s = np.asarray(probe["index_scores"])
        want_sel = np.asarray(probe["selected"])
        live = np.isfinite(want_s)
        gs = np.where(live, got["index_scores"], 0.0)
        per.setdefault("index_score_rel_l2", []).append(
            rel(gs, np.where(live, want_s, 0.0)))
        both = (got["selected"] & want_sel).sum(axis=1)
        per.setdefault("index_overlap_min", []).append(
            float((both / np.maximum(want_sel.sum(axis=1), 1)).min()))
        per.setdefault("verify_live_mismatch", []).append(float(np.sum(
            np.isfinite(got["index_scores"][n_prefill:])
            != live[n_prefill:])))
        again = blocks.attention(i, y, given=probe["selected"])
        hold("given_selection_prefill", again["out"], a,
             slice(None, n_prefill))
        hold("given_selection_decode", again["out"], a, one_rows)
        hold("verify_rel_l2", again["out"], a, two_rows)
        rows, pos = blocks.stream(i, y)
        want_rows, want_pos = expected_stream(reference, canonical, arch,
                                              quant, i, y, n_prefill)
        worst = max(
            float(np.max(np.linalg.norm(g - w, axis=1)
                         / np.maximum(np.linalg.norm(w, axis=1), 1e-30)))
            for g, w in zip(rows, want_rows))
        per.setdefault("accepted_stream", []).append(
            worst + float(pos != want_pos))
        x = x + a
        h = ref(norm, x, lp["post_attention_layernorm"])
        f = ref(ff, h.astype(jnp.float32), lp, ex)
        got_f = blocks.feed_forward(i, h)
        hold("ffn_prefill", got_f, f, slice(None, n_prefill))
        hold("ffn_decode", got_f, f, slice(n_prefill, None))
        x = x + f
    xb = x.astype(jnp.bfloat16)[-DECODE_ROWS - 8:]
    for which, name in (("main", "head_rel_l2"), (MTP, "mtp_rel_l2")):
        if which == MTP and MTP not in bodies:
            continue
        norm_w = (canonical["norm"] if which == "main"
                  else canonical["mtp"]["shared_head_norm"])
        want = reference._head(canonical, arch, quant,
                               xb.astype(jnp.float32), norm_w)
        per.setdefault(name, []).append(
            rel(blocks.head(xb, which), np.asarray(want)))
    found = {k: (min(v) if k in LOWER_IS_FLOOR else max(v))
             for k, v in per.items()}
    return {"found": found, "layers": per,
            "checked_layers": [str(b) for b in bodies]}


def _within(found, limits) -> bool:
    return all(k in found and (found[k] >= v if k in LOWER_IS_FLOOR
                               else found[k] <= v)
               for k, v in limits.items())


def layer_check(config: Dict[str, Any], canonical: Dict[str, Any],
                seed: int, stand_in=None) -> Dict[str, Any]:
    """The check of ``config`` on the canonical tree of ``seed``: the
    program's blocks (or ``stand_in``) against the reference's, with the
    limits and the verdict."""
    import time

    from harness import reference_deepseek_v32 as reference
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
    """A note line with every checked body's reading; returns each
    compared number beside its limit, ``(name, value, limit[,
    "floor"])``, for ``common.print_compared`` or the runner's last
    lines."""
    from harness import common

    common.note(info="layer_check", found=check["found"],
                limits=check["limits"], within=check["within"],
                checked_layers=check["checked_layers"],
                seconds=check["seconds"], layers=check["layers"])
    plain = ("index_", "verify_live", "accepted_")
    return [(k, check["found"].get(k), limit, "floor")
            if k in LOWER_IS_FLOOR else
            (k if k.startswith(plain) else f"layer_rel_l2.{k}",
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

    from harness import common, spec, weights_deepseek_v32 as weights

    config = json.loads(
        (here / "configs" / f"{args.config}.json").read_text())
    if args.tiny:
        config = spec.deep_update(config, config["tiny"])
    arch = config["reference"]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    canonical = weights.canonical_params(config, args.seed, check=False)
    n_prefill = prefill_rows(int(config["engine"]["max_seq"]))
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
                            stand_in=AlteredReference(
                                arch, quant, canonical, CONTROLS[name],
                                n_prefill))
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
