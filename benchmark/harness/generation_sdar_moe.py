"""The ``generation`` module of SDAR-MoE: a family whose step is a BLOCK.
A step of the program denoises the ``B`` rows of a slot's block (MASK
ids where nothing is committed), commits the rows its confidences
choose, and stores the block's K/V with one more pass when no MASK is
left; the logit row at position ``i`` is of the token AT position ``i``.
``harness/__init__.py`` has the role's contract.

Check (a), ``program_rows`` / ``reference_rows``: the family's prefill
of the first ``SEQUENCE[0]`` ids (eight whole blocks; the LAST row's
logits are ``"prefill"``), then the other ids two blocks of four through
a cache of the kind, type and length the cell's engine holds, each block
holding MASK at positions drawn from the ids themselves (``noised``)
and STORED as it stands, so that the second block reads the first one's
noised K/V (``"block"`` ``[8, V]``); the reference is ONE pass over the
same ids under the block-causal mask.

Check (b), ``served_gaps``: a served request is replayed pass by pass
from the ``steps`` its stream events carried (the count of passes the
request had been given when each token was committed). Block ``k``'s
first pass is the pass after the store of block ``k - 1`` (the request's
first pass is 1), so a token's ``steps`` says at which denoise pass
``p`` of its block it was committed; that pass saw the prompt's tail
and the tokens of passes before ``p`` in place, MASK elsewhere, and
every earlier block final. The reference carries, layer by layer, the
final sequence and one noised copy of the generated stretch for each
pass index (``reference_sdar_moe.replay``), at ONE padded length. A
served token made TWO choices of the program's, and its gap is the sum
of what each lies below the reference's, both in standard deviations of
the row's logits on the copy of its own pass: WHICH TOKEN (the row's
best logit less the token's) and WHICH ROW (``transfer_gaps``: the
reference's confidences of the rows that pass saw MASK, through the
reference's own transfer rule, name the rows to commit; a row committed
in their place lies its log confidence below theirs). A program that
commits the first rows, the least confident rows or ignores the count
reads a row gap on most blocks and a token gap of 0. What cannot be
known: a request that ends inside a block (``max_tokens``) was sent the
block's first rows only, and a row that was never sent is MASK in every
copy, also where the pass saw a committed token there; that block's
rows get no row gap (PERF.md 7).
"""

from __future__ import annotations

from typing import Any, Dict, List

SEQUENCE = (32, 8)


def noised(ids, n_prompt: int, block: int, mask_id: int) -> List[int]:
    """``ids`` with MASK at one to ``block - 1`` rows of every block
    after the prompt, drawn from the ids themselves (both sides of the
    comparison make the same draw from what they are both given)."""
    import numpy as np

    out = [int(t) for t in ids]
    rng = np.random.default_rng([int(t) for t in ids[:8]] + [29])
    for lo in range(n_prompt, len(out), block):
        for j in rng.choice(block, int(rng.integers(1, block)),
                            replace=False):
            out[lo + int(j)] = mask_id
    return out


def program_rows(model, eng_cfg: Dict[str, Any], ids, seed: int
                 ) -> Dict[str, Any]:
    """The program's prefill of the first ``SEQUENCE[0]`` ids into a new
    slab of ``max_seq`` positions, then the noised blocks one block pass
    at a time through it: ``{"prefill": [V], "block": [8, V]}``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    del seed
    family, cfg = model.family, model.config
    spec = family.block_spec(cfg)
    b, n_prompt = spec.length, SEQUENCE[0]
    if n_prompt % b or len(ids) % b:
        raise ValueError(f"SEQUENCE {SEQUENCE} in blocks of {b}")
    seq = noised(ids, n_prompt, b, spec.mask_id)
    cache = family.new_cache(cfg, 1, int(eng_cfg["max_seq"]),
                             eng_cfg.get("kv_cache_dtype", "bf16"))
    fwd = jax.jit(family.forward, static_argnums=1)
    lg, cache = fwd(model.params, cfg,
                    jnp.asarray(seq[:n_prompt], jnp.int32)[None], cache)
    rows = {"prefill": np.asarray(lg[0, -1], np.float32)}
    blocks = []
    for lo in range(n_prompt, len(seq), b):
        lg, cache = fwd(model.params, cfg,
                        jnp.asarray(seq[lo:lo + b], jnp.int32)[None], cache)
        blocks.append(np.asarray(lg[0], np.float32))
    rows["block"] = np.concatenate(blocks)
    return rows


def reference_rows(reference, canonical, arch: Dict[str, Any],
                   quant: Dict[str, Any], ids) -> Dict[str, Any]:
    """The same rows from ONE pass of the reference over the same ids
    under the block-causal mask."""
    import numpy as np

    n_prompt = SEQUENCE[0]
    seq = noised(ids, n_prompt, int(arch["block"]),
                 int(arch["mask_token_id"]))
    ref = np.asarray(reference.all_logits(canonical, arch, quant, seq,
                                          first=n_prompt - 1))
    return {"prefill": ref[0], "block": ref[1:]}


def pass_index(steps: List[int], n_prompt: int, block: int, passes: int
               ) -> List[int]:
    """At which denoise pass of its block (0 ..) each served token was
    committed, from the request's pass counts ``steps``: block ``k``'s
    first pass is the one after the store of block ``k - 1``."""
    out, first, at = [], 1, 0
    room = block - n_prompt % block          # the first block's MASK rows
    while at < len(steps):
        rows = steps[at:at + room]
        for s in rows:
            p = s - first
            if not 0 <= p < passes:
                raise ValueError(
                    f"steps {rows} of the block that opens at pass "
                    f"{first}: a block has {passes} denoise passes")
            out.append(p)
        first = max(rows) + 2
        at, room = at + room, block
    return out


def staged(sample: Dict[str, Any], arch: Dict[str, Any], padded: int
           ) -> Dict[str, Any]:
    """What the replay of ``sample`` is asked: the ``final`` sequence at
    the padded length, one noised copy a denoise pass (``copies`` ``[T,
    G]`` from position ``start`` on, whose first ``tail`` rows are the
    prompt's), the ``targets`` and the pass of each served token
    (``at_pass``)."""
    import numpy as np

    b, mask = int(arch["block"]), int(arch["mask_token_id"])
    passes = int(arch["denoising_steps"])
    prompt = [int(t) for t in sample["prompt"]]
    tokens = [int(t) for t in sample["tokens"]]
    steps = sample.get("steps")
    if not steps or len(steps) != len(tokens):
        raise ValueError("a block family's stream says `steps`, one "
                         "integer a token: none on this record")
    at_pass = pass_index([int(s) for s in steps], len(prompt), b, passes)
    seq = prompt + tokens
    padded = max(int(padded), -(-len(seq) // b) * b)
    start = len(prompt) // b * b
    # the stretch every noised copy covers: half the padded length,
    # where the answer fits it (one shape whatever the prompt's length)
    stretch = padded // 2 if len(seq) - start <= padded // 2 else padded
    copies = np.full((passes, stretch), mask, np.int64)
    targets = np.zeros((stretch,), np.int64)
    tail = len(prompt) - start
    copies[:, :tail] = prompt[start:]
    for j, (tok, p) in enumerate(zip(tokens, at_pass)):
        copies[p + 1:, tail + j] = tok
        targets[tail + j] = tok
    return {"final": seq + [mask] * (padded - len(seq)), "copies": copies,
            "start": start, "tail": tail, "targets": targets,
            "at_pass": at_pass, "steps": [int(s) for s in steps],
            # stretch row -> pass, and the rows of blocks known whole
            "committed": {tail + j: p for j, p in enumerate(at_pass)},
            "whole": (tail + len(at_pass)) // b * b}


def transfer_gaps(reference, found: Dict[str, Any], copies, committed,
                  whole: int, arch: Dict[str, Any]) -> Dict[int, float]:
    """How far below the reference's choice of ROWS each committed row
    lies: ``{row: gap}`` over the stretch's first ``whole`` rows (whole
    blocks whose every row is known). ``committed`` is ``{row: pass}``.
    At every pass the reference's rule (``reference.transfer``, on the
    reference's own confidences of the rows that pass saw MASK) names
    the rows to commit; a committed row among them reads 0, any other
    what its log confidence lies below the least confident of them, in
    standard deviations of its own logits: the unit of a token's gap,
    and like it 0 where the program chose what the reference does."""
    import numpy as np

    b, mask = int(arch["block"]), int(arch["mask_token_id"])
    passes = int(arch["denoising_steps"])
    rule = arch["remasking_strategy"]
    threshold = float(arch["confidence_threshold"])
    conf = np.asarray(found["confidence"], np.float64)
    spread = np.asarray(found["spread"], np.float64)
    out: Dict[int, float] = {}
    for lo in range(0, whole, b):
        for p in range(passes):
            rows = [j for j in range(lo, lo + b) if copies[p][j] == mask]
            took = [j for j in rows if committed.get(j) == p]
            if not took:
                continue
            want = {rows[i] for i in reference.transfer(
                np.exp(conf[p, rows]), reference.owed(p, b, passes), rule,
                threshold)}
            floor = min(conf[p, j] for j in want)
            for j in took:
                out[j] = 0.0 if j in want else max(
                    0.0, floor - conf[p, j]) / max(spread[p, j], 1e-30)
    return out


def served_gaps(reference, canonical, arch: Dict[str, Any],
                quant: Dict[str, Any], sample: Dict[str, Any],
                padded: int) -> Dict[str, List[float]]:
    """A gap for every served token of ``sample``: what the token lies
    below the reference's best at the pass that committed it, plus what
    its ROW lies below the rows the reference's transfer rule commits
    at that pass (module docstring); ``first`` the tokens of the
    request's first pass."""
    at = staged(sample, arch, padded)
    found = reference.replay(canonical, arch, quant, at["final"],
                             at["copies"], at["start"], at["targets"])
    rows = transfer_gaps(reference, found, at["copies"], at["committed"],
                         at["whole"], arch)
    return summed(found["gap"], rows, at)


def summed(token, rows: Dict[int, float], at: Dict[str, Any]
           ) -> Dict[str, List[float]]:
    """Each served token's two gaps as one: ``token`` ``[T, G]`` read on
    the copy of its own pass, plus its row's of ``rows``; the tokens of
    the request's first pass apart from the later ones."""
    import numpy as np

    token, tail = np.asarray(token, np.float64), at["tail"]
    gaps = [float(token[p, tail + j]) + rows.get(tail + j, 0.0)
            for j, p in enumerate(at["at_pass"])]
    lead = [s == min(at["steps"]) for s in at["steps"]]
    return {"first": [g for g, f in zip(gaps, lead) if f],
            "later": [g for g, f in zip(gaps, lead) if not f]}
