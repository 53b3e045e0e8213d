"""SDAR-MoE held to its reference one block of a layer at a time, on the
SAME input, where the router's coin cannot fall.

Why. As ``checks_afmoe``: the model chooses 8 of 128 experts a row and
layer, 48 times a row; the program's hidden state drifts a few per cent
from the float32 reference's through the layers and some row in most
sequences has its eighth and ninth expert closer than that, so the
end-to-end comparisons hold garbage off and cannot see a precision, a
causal mask in the block-causal one's place or a dropped QK norm. This
check can: each block of a layer gets the reference's own input, rounded
to bfloat16 so that both sides read the same numbers, and its output is
held to the reference's for that input.

Which layers. The model has ONE kind of layer: layer 0, once.

What runs, at sizes where the mechanisms bind. A seeded sequence of
``prefill_rows`` tokens and ``block_rows`` more (1,024 + 256 at the
cell's sizes: one 1024-row chunk, half the slab at most, then 64 blocks
of four rows, enough rows that a router in a lower precision swaps an
expert among them: 12 rows held no swap, my chip run, PR 53) walks the
reference's layer;

- attention: the program's ``attention_block`` prefills the first rows
  in the cell's chunks (``engine.prefill_chunk``; ``ops/swa.full_chunk``
  under the block-causal ``live``) into a NEW one-layer private cache of
  the cell's length, splices it into a one-slot slab as ``engine_insert``
  does and then takes the last rows a BLOCK at a time through the slab
  with per-slot positions as the engine's block pass does
  (``ops/swa.full_block``: ``decode_attention_lanes`` at ``B x H``
  heads under one limit);
- experts: the program's ``moe_block`` (softmax router in float32, top 8
  renormalised, ``routed_experts`` on the stacks where they lie) on the
  first rows as chunks and on the last rows as slots of one block each.

- the transfer (``transfer_apart``): ``TRANSFER_PASSES`` passes of the
  cell's ``max_batch`` blocks, on seeded LOGITS of the cell's vocabulary
  whose confidences lie far apart (a bump of 0 to 16 on one column a
  row: probabilities from 1e-4 to over the threshold), MASK flags and
  pass indices drawn from the seed, half the passes the all-greedy
  program and half a mixed one (slots at temperature 0 and at 1.0): the
  engine's own device functions (``_block_sample``: token and
  confidence; ``_block_transfer``: the rows a pass commits) against
  plain float64 arithmetic and ``reference.transfer``. The reading is
  the share of blocks on which a committed row or a greedy row's token
  differs. It holds on the chip what the end-to-end replay cannot on
  seeded weights, where the MASK rows of a block lie closer together in
  confidence than the program lies to the reference (PERF.md 6, PR 53).

Compared: each reading against ``reference_sdar_moe.layer_limits``.
``stand_in`` puts something else in the program's place through the same
comparison: the reference with a planted fault or a lower precision
(``CONTROLS``).

The END-TO-END limits' controls (``generation_controls``): the same
stand-ins, a third that commits the first rows in the most confident
ones' place (``sequential``) and a fourth whose tokens are not the
model's (``wrong_token``), through check (a) and check (b)
at the configuration's own widths and depth, so that ``reference_sdar_
moe.tolerance`` and ``served_gap_limits`` lie between what the program
reads and what each of these reads. Check (a): the altered reference's
rows of the runner's sequence against the sound reference's. Check (b),
teacher-forced: a seeded request (ids and a schedule of commits drawn
from the seed, no program) is staged as ``served_gaps`` stages a served
one; at the state each pass saw, the stand-in's token is the altered
reference's best and its rows those the altered confidences choose, and
both are read against the sound reference as a served token's are.

As a command (``python3 benchmark/harness/checks_sdar_moe.py --config
<name> --seed n [--controls a,b] [--generation] [--tiny]``) it runs the
sound program and then each control; each prints one line, and the last
line says whether every control came out NOT within the limits. With
``--generation`` it reads the end-to-end controls instead (no program
runs), one line each.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import Any, Dict

if __name__ == "__main__":      # run as a command: the harness is two up
    _bench = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_bench), str(_bench.parent)]

from harness.checks_mimo_v2 import _within, check_ids, report  # noqa: F401

PREFILL_ROWS = 2048
BLOCKS = 64
CHECKED_LAYER = 0
# name -> the reference's ``alter``: each must come out not within
CONTROLS = {
    "causal_mask": {"mask": "causal"},
    "router_bf16": {"router_dtype": "bfloat16"},
    "no_qk_norm": {"qk_norm": False},
    "sequential": {"rule": "sequential"},
}
TRANSFER_PASSES = 32


def prefill_rows(max_seq: int, chunk: int) -> int:
    return max(chunk, min(PREFILL_ROWS, max_seq // 2) // chunk * chunk)


def block_rows(max_seq: int, chunk: int, block: int) -> int:
    """Rows that go through the cache a block at a time after the
    prefill's: ``BLOCKS`` blocks, or what the slab has room for."""
    return min(BLOCKS * block,
               (max_seq - prefill_rows(max_seq, chunk)) // block * block)


class ProgramBlocks:
    """The program's blocks of the checked layer on the canonical tree:
    ``cfg`` the family's config, ``max_seq`` the slab's, ``chunk`` the
    engine's prefill chunk."""

    def __init__(self, cfg, canonical: Dict[str, Any], max_seq: int,
                 chunk: int, kv: str = "bf16"):
        import jax

        from bigdl_tpu.models import sdar_moe as prog

        self.prog, self.cfg, self.canonical = prog, cfg, canonical
        self.max_seq, self.chunk, self.kv = max_seq, chunk, kv
        self._attn = jax.jit(
            lambda lp, y, cache, one: prog.attention_block(
                y, lp, one, cache, prog.FULL), static_argnums=3)
        self._moe = jax.jit(
            lambda lp, experts, h: prog.moe_block(
                h, lp, experts, CHECKED_LAYER, cfg)[0])

    def attention(self, y):
        """Outputs ``[S, D]`` of the rows of ``y``."""
        import numpy as np

        from bigdl_tpu.ops.kvcache import init_cache_spec

        lp = self.prog.prepare_layer(
            self.prog.layer_leaves(self.canonical, self.cfg, CHECKED_LAYER))
        one = dataclasses.replace(self.cfg, num_hidden_layers=1)
        spec = self.prog.cache_spec(one)
        cache = init_cache_spec(spec.unrolled(), 1, self.max_seq,
                                kv_cache_dtype=self.kv)
        p, b = prefill_rows(self.max_seq, self.chunk), self.cfg.block_length
        pieces = ([(a, a + self.chunk) for a in range(0, p, self.chunk)]
                  + [(a, a + b) for a in range(p, y.shape[0], b)])
        rows = []
        for lo, hi in pieces:
            if lo == p:
                cache = init_cache_spec(
                    spec, 1, self.max_seq, kv_cache_dtype=self.kv,
                    per_slot_pos=True).spliced(cache, 0, p)
            out, cache = self._attn(lp, y[None, lo:hi], cache, one)
            rows.append(np.asarray(out[0], np.float32))
        return np.concatenate(rows)

    def feed_forward(self, h):
        import numpy as np

        lp = self.prog.layer_leaves(self.canonical, self.cfg, CHECKED_LAYER)
        run = lambda x: self._moe(lp, self.canonical["experts"],  # noqa: E731
                                  x)
        p, b = prefill_rows(self.max_seq, self.chunk), self.cfg.block_length
        parts = [np.asarray(run(h[None, a:a + self.chunk])[0], np.float32)
                 for a in range(0, p, self.chunk)]
        tail = h[p:]
        parts.append(np.asarray(run(tail.reshape(-1, b, tail.shape[-1])),
                                np.float32).reshape(tail.shape))
        return np.concatenate(parts)


    def choose(self, logits, masked, s, temps, seeds, all_greedy: bool):
        """The tokens ``[N, B]`` the engine's sampler draws for the
        blocks' rows and the rows ``[N, B]`` its transfer rule commits
        (``transfer_passes`` has the arguments)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from bigdl_tpu.serving import engine

        spec = self.prog.block_spec(self.cfg)
        if getattr(self, "_choose", None) is None:
            def choose(logits, masked, s, temps, seeds, all_greedy):
                n = logits.shape[0]
                x0, conf = engine._block_sample(
                    logits, spec, temps, jnp.zeros((n,), jnp.int32),
                    jnp.ones((n,), jnp.float32), seeds,
                    jnp.zeros((n,), jnp.int32), all_greedy)
                return x0, engine._block_transfer(conf, masked, s, spec)

            self._choose = jax.jit(choose, static_argnums=5)
        x0, commit = self._choose(
            jnp.asarray(logits, jnp.float32), jnp.asarray(masked),
            jnp.asarray(s, jnp.int32), jnp.asarray(temps, jnp.float32),
            jnp.asarray(seeds, jnp.int32), all_greedy)
        return np.asarray(x0), np.asarray(commit)


def reference_choice(logits, masked, s, temps, tokens, arch, rule=None):
    """The same in plain float64 arithmetic: a greedy slot's tokens are
    the rows' best candidates, a sampled slot's are given (``tokens``,
    the program's draw: no plain arithmetic repeats a seeded stream); a
    row's confidence is its token's probability under the softmax of
    its logits (temperature 1.0, no top-k or top-p: the cell's sampled
    half), the MASK id no candidate; the rows committed are
    ``reference.transfer``'s."""
    import numpy as np

    from harness import reference_sdar_moe as reference

    b, passes = int(arch["block"]), int(arch["denoising_steps"])
    lg = np.asarray(logits, np.float64)
    lg[..., int(arch["mask_token_id"])] = -np.inf
    best = lg.argmax(-1)
    x0 = best if tokens is None else np.where(
        np.asarray(temps)[:, None] <= 0, best, tokens)
    top = lg.max(-1, keepdims=True)
    lse = top[..., 0] + np.log(np.exp(lg - top).sum(-1))
    conf = np.exp(np.take_along_axis(lg, x0[..., None], -1)[..., 0] - lse)
    commit = np.zeros(masked.shape, bool)
    for i in range(masked.shape[0]):
        rows = np.flatnonzero(masked[i])
        for j in reference.transfer(
                conf[i, rows], reference.owed(int(s[i]), b, passes),
                rule or arch["remasking_strategy"],
                float(arch["confidence_threshold"])):
            commit[i, rows[j]] = True
    return x0, commit


def transfer_passes(seed: int, slots: int, arch: Dict[str, Any]):
    """``TRANSFER_PASSES`` seeded passes of ``slots`` blocks: ``(logits
    [N, B, V] float32 of bfloat16's precision, as the family's forward
    gives them, masked [N, B], s [N], temps [N], seeds [N],
    all_greedy)``. Every block holds a MASK row; an
    all-greedy pass holds no sampled slot, a mixed one both kinds."""
    import jax.numpy as jnp
    import numpy as np

    b, v = int(arch["block"]), int(arch["vocab"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 37])
    for p in range(TRANSFER_PASSES):
        lg = rng.normal(size=(slots, b, v)).astype(np.float32)
        col = rng.integers(1, v, (slots, b))
        np.put_along_axis(lg, col[..., None],
                          rng.uniform(0, 16, (slots, b, 1)), -1)
        # (both sides read the numbers the program's dtype holds)
        lg = np.asarray(jnp.asarray(lg, jnp.bfloat16).astype(jnp.float32))
        masked = rng.random((slots, b)) < 0.7
        masked[np.arange(slots), rng.integers(0, b, slots)] = True
        greedy = p % 2 == 0
        temps = np.zeros(slots) if greedy else (np.arange(slots) % 2
                                                ).astype(np.float64)
        yield (lg, masked,
               rng.integers(0, int(arch["denoising_steps"]), slots), temps,
               rng.integers(0, 2 ** 31 - 1, slots), greedy)


def transfer_apart(blocks, seed: int, slots: int, arch: Dict[str, Any]
                   ) -> float:
    """The share of the seeded blocks on which ``blocks.choose`` commits
    another row than the reference's rule, or gives a greedy row another
    token than its best candidate."""
    import numpy as np

    apart = total = 0
    for lg, masked, s, temps, seeds, greedy in transfer_passes(seed, slots,
                                                               arch):
        x0, commit = blocks.choose(lg, masked, s, temps, seeds, greedy)
        want_x0, want = reference_choice(lg, masked, s, temps, x0, arch)
        apart += int(((commit != want) | (x0 != want_x0)).any(-1).sum())
        total += len(s)
    return apart / total


class AlteredReference:
    """A control: the reference itself with ``alter`` (a planted fault
    or a precision below the configuration's) in the program's place."""

    def __init__(self, arch, quant, canonical, alter):
        import jax
        import jax.numpy as jnp

        from harness import reference_sdar_moe as reference

        alter = dict(alter)
        if isinstance(alter.get("router_dtype"), str):
            alter["router_dtype"] = jnp.dtype(alter["router_dtype"])
        self.arch, self.alter = arch, alter
        _, self.lp, self.ex = next(iter(reference.layer_stack(canonical,
                                                              arch)))
        self._attn = jax.jit(lambda y, lp: reference.attention(
            y, lp, arch, quant, alter))
        self._ff = jax.jit(lambda h, lp, ex: reference.feed_forward(
            h, lp, ex, arch, quant, alter))

    def attention(self, y):
        import jax
        import jax.numpy as jnp
        import numpy as np

        with jax.default_matmul_precision("highest"):
            return np.asarray(self._attn(y.astype(jnp.float32), self.lp))

    def feed_forward(self, h):
        import jax
        import jax.numpy as jnp
        import numpy as np

        with jax.default_matmul_precision("highest"):
            return np.asarray(self._ff(h.astype(jnp.float32), self.lp,
                                       self.ex))


    def choose(self, logits, masked, s, temps, seeds, all_greedy: bool):
        """The reference's own choice, under ``alter``'s ``rule``; a
        sampled row's token its best candidate (no stream to follow)."""
        import numpy as np

        del seeds, all_greedy
        return reference_choice(
            logits, masked, s, np.zeros_like(np.asarray(temps)), None,
            self.arch, self.alter.get("rule"))


def layer_errors(blocks, canonical: Dict[str, Any], arch: Dict[str, Any],
                 quant: Dict[str, Any], ids, n_prefill: int
                 ) -> Dict[str, Any]:
    """``blocks`` against the reference's blocks on the same inputs, the
    checked layer's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import reference_sdar_moe as reference

    eps = float(arch["norm_eps"])
    norm = jax.jit(lambda x, w: reference._rms_norm(x, w, eps))
    attn = jax.jit(lambda y, lp: reference.attention(y, lp, arch, quant))
    ff = jax.jit(lambda h, lp, ex: reference.feed_forward(h, lp, ex, arch,
                                                          quant))

    def ref(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    found: Dict[str, float] = {}

    def hold(kind, got, want):
        want = np.asarray(want)
        for part, sl in (("prefill", slice(None, n_prefill)),
                         ("block", slice(n_prefill, None))):
            found[f"{kind}_{part}"] = reference.relative_l2(got[sl],
                                                            want[sl])

    _, lp, ex = next(iter(reference.layer_stack(canonical, arch)))
    x = reference.embed(canonical, ids)
    y = ref(norm, x, lp["input_layernorm"]).astype(jnp.bfloat16)
    a = ref(attn, y.astype(jnp.float32), lp)
    hold("attention", blocks.attention(y), a)
    x = x + a
    h = ref(norm, x, lp["post_attention_layernorm"]).astype(jnp.bfloat16)
    hold("ffn", blocks.feed_forward(h), ref(ff, h.astype(jnp.float32), lp,
                                            ex))
    return {"found": found, "layers": {k: [v] for k, v in found.items()},
            "checked_layers": [CHECKED_LAYER]}


def layer_check(config: Dict[str, Any], canonical: Dict[str, Any],
                seed: int, stand_in=None) -> Dict[str, Any]:
    """The check of ``config`` on the canonical tree of ``seed``: the
    program's blocks (or ``stand_in``) against the reference's, with the
    limits and the verdict."""
    import time

    from harness import reference_sdar_moe as reference
    from harness.weights import _family_config

    t_start = time.monotonic()
    arch, eng = config["reference"], config["engine"]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    max_seq, chunk = int(eng["max_seq"]), int(eng.get("prefill_chunk", 256))
    n_prefill = prefill_rows(max_seq, chunk)
    if stand_in is None:
        _, cfg, _ = _family_config(config)
        stand_in = ProgramBlocks(cfg, canonical, max_seq, chunk,
                                 eng.get("kv_cache_dtype", "bf16"))
    ids = check_ids(seed, int(arch["vocab"]),
                    n_prefill + block_rows(max_seq, chunk,
                                           int(arch["block"])))
    out = layer_errors(stand_in, canonical, arch, quant, ids, n_prefill)
    out["found"]["transfer_apart"] = transfer_apart(
        stand_in, seed, int(eng["max_batch"]), arch)
    out["layers"]["transfer_apart"] = [out["found"]["transfer_apart"]]
    out["limits"] = reference.layer_limits(config)
    out["within"] = _within(out["found"], out["limits"])
    out["seconds"] = time.monotonic() - t_start
    return out


# name -> what stands in the program's place end to end: the reference's
# ``alter``, or another transfer rule
GENERATION_CONTROLS = {
    "causal_mask": {"alter": CONTROLS["causal_mask"]},
    "router_bf16": {"alter": CONTROLS["router_bf16"]},
    "sequential": {"rule": "sequential"},
    # tokens of another row, pass or slot: ids drawn from the seed
    "wrong_token": {"tokens": "seeded"},
}
# the seeded request of check (b)'s controls: a prompt with a tail of
# two rows, an answer that ends inside a block
CONTROL_REQUEST = (510, 255)


class _Altered:
    """The reference with ``alter`` on every call that takes one."""

    def __init__(self, alter):
        import jax.numpy as jnp

        alter = dict(alter)
        if isinstance(alter.get("router_dtype"), str):
            alter["router_dtype"] = jnp.dtype(alter["router_dtype"])
        self.alter = alter

    def __getattr__(self, name):
        import functools

        from harness import reference_sdar_moe as reference

        return functools.partial(getattr(reference, name), alter=self.alter)


def seeded_request(seed: int, arch: Dict[str, Any]) -> Dict[str, Any]:
    """A request no program served: prompt and tokens drawn from
    ``seed``, and ``steps`` of a schedule the family's loop could have
    followed (every denoise pass commits its count ``owed`` of the rows
    still MASK, drawn from the seed; a storing pass after the last)."""
    import numpy as np

    from harness import reference_sdar_moe as reference

    b, passes = int(arch["block"]), int(arch["denoising_steps"])
    n_prompt, n_tokens = CONTROL_REQUEST
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 31])
    ids = [int(t) for t in rng.integers(1, int(arch["vocab"]),
                                        n_prompt + n_tokens)]
    steps, given, room = [], 0, b - n_prompt % b
    while len(steps) < n_tokens:
        left = [int(j) for j in rng.permutation(room)]
        at = [0] * room
        for p in range(passes):
            n = min(reference.owed(p, b, passes), len(left))
            for j in left[:n]:
                at[j] = given + p + 1
            left = left[n:]
        steps.extend(at)
        given, room = max(at) + 1, b
    return {"prompt": ids[:n_prompt], "tokens": ids[n_prompt:],
            "steps": steps[:n_tokens]}


def generation_controls(config: Dict[str, Any], canonical: Dict[str, Any],
                        seed: int, names=None) -> Dict[str, Dict[str, Any]]:
    """What each of ``GENERATION_CONTROLS`` reads through check (a)
    (``rel_l2`` by name) and check (b) (``served``: the three numbers of
    ``served.compare``) in the program's place (module docstring), with
    the limits each is held to and which of them it is over."""
    import numpy as np

    from harness import (generation_sdar_moe as generation,
                         reference_sdar_moe as reference, serve_runner,
                         served)

    arch = config["reference"]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    b, passes = int(arch["block"]), int(arch["denoising_steps"])
    kv = config["engine"].get("kv_cache_dtype", "bf16")
    limits = dict(reference.served_gap_limits(config, kv),
                  rel_l2=reference.tolerance(config, kv))
    n_prompt, later = serve_runner.sequence_of(generation)
    ids = serve_runner.check_ids(seed, int(arch["vocab"]), n_prompt + later)
    sound_rows = generation.reference_rows(reference, canonical, arch, quant,
                                           ids)
    sample = seeded_request(seed, arch)
    at = generation.staged(sample, arch, served.pad_length(
        sum(CONTROL_REQUEST)))
    whole = at["whole"]

    def replay(ref, targets):
        return {k: np.asarray(v) for k, v in ref.replay(
            canonical, arch, quant, at["final"], at["copies"], at["start"],
            targets).items()}

    sound = replay(reference, at["targets"])
    out: Dict[str, Dict[str, Any]] = {}
    for name in names or GENERATION_CONTROLS:
        how = GENERATION_CONTROLS[name]
        found: Dict[str, Any] = {}
        if "alter" in how:
            ref = _Altered(how["alter"])
            found["rel_l2"] = serve_runner.rows_errors(
                generation.reference_rows(ref, canonical, arch, quant, ids),
                sound_rows)
            theirs = replay(ref, at["targets"])
            # the stand-in's tokens, read against the sound reference
            token = replay(reference, theirs["best"])["gap"]
        elif "tokens" in how:
            theirs = sound
            token = replay(reference, np.random.default_rng(
                [seed & 0xFFFFFFFF, 41]).integers(
                    1, int(arch["vocab"]), sound["gap"].shape))["gap"]
        else:
            theirs, token = sound, np.zeros_like(sound["gap"])
        # the stand-in's rows: its own rule on its own confidences, at
        # the first pass of every whole block (a later pass of this
        # family's two commits what is left)
        rule = how.get("rule", arch["remasking_strategy"])
        took = {}
        for lo in range(0, whole, b):
            rows = [j for j in range(lo, lo + b)
                    if at["copies"][0][j] == int(arch["mask_token_id"])]
            for i in reference.transfer(
                    np.exp(theirs["confidence"][0, rows]),
                    reference.owed(0, b, passes), rule,
                    float(arch["confidence_threshold"])):
                took[rows[i]] = 0
        rows = generation.transfer_gaps(reference, sound, at["copies"],
                                        took, whole, arch)
        g = generation.summed(token, rows, at)
        found["served"] = {"prefill_gap_max": max(g["first"]),
                           "decode_gap_max": max(g["later"]),
                           "decode_gap_mean": (sum(g["later"])
                                               / len(g["later"]))}
        over = [k for k in found["served"] if found["served"][k] > limits[k]]
        over += [f"rel_l2.{k}" for k, v in found.get("rel_l2", {}).items()
                 if v is None or v > limits["rel_l2"]]
        found["over"] = over
        out[name] = found
    return {"controls": out, "limits": limits}


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
    ap.add_argument("--generation", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    from harness import common, spec, weights_sdar_moe as weights

    if not args.tiny:
        import os

        import jax

        # the benchmark's compile cache (`run.py`): a second seed's
        # programs, and the cell's own reference passes, are found again
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              str(here / ".cache" / "jax"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    config = json.loads(
        (here / "configs" / f"{args.config}.json").read_text())
    if args.tiny:
        config = spec.deep_update(config, config["tiny"])
    arch = config["reference"]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    canonical = weights.canonical_params(config, args.seed, check=False)
    if args.generation:
        names = [c for c in args.controls.split(",") if c]
        found = generation_controls(
            config, canonical, args.seed,
            names if names != list(CONTROLS) else None)
        for name, what in found["controls"].items():
            print(json.dumps(dict(what, control=name, seed=args.seed)),
                  flush=True)
        print(json.dumps({
            "seed": args.seed, "limits": found["limits"],
            "controls_refused": {k: bool(v["over"])
                                 for k, v in found["controls"].items()},
            "correct": all(v["over"] for v in found["controls"].values())}),
            flush=True)
        return 0
    sound = None
    if not args.skip_sound:
        check = layer_check(config, canonical, args.seed)
        common.print_compared(report(check))
        sound = check["within"]
        print(json.dumps({"control": None, "seed": args.seed,
                          "found": check["found"],
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
