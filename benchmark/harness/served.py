"""What the timed path served, held against the plain reference.

Once the window has closed, a sample of the greedy requests it
finished (drawn from the seed, the longest among them) goes through the
configuration's reference: ONE full forward pass over a request's
prompt and the tokens the server streamed for it. At every served
position the reference has a best logit, and the served token's logit
lies some way below it: 0 where the server picked the reference's own
best, a little where rounding swapped two near-equal logits, several
standard deviations where a token came from a wrong row, page, layer or
position of the cache. That gap, in standard deviations of the
reference's logits at the position, is what is compared: never equality
of tokens, which flips on rounding at random weights.

The first served token of a request is the prefill's (through the
prefix cache where the configuration shares prefixes), every later one
a decode step's through the cache the cell times, in the batch the
window happened to hold. Nothing here is architecture: the reference
module is the cell's, and so is the function that turns one sampled
request into gaps (the ``generation`` role's ``served_gaps``; what is
said above is its default, ``next_token_gaps``).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Sequence

import numpy as np

SAMPLE_REQUESTS = 4
# The reference compiles one program per sequence length (a minute for
# each in the long-context cell: my chip run, PR 38), and a sample drawn
# from the seed has new lengths in every run. So every request of a
# sample is padded at its END (causal attention: no compared position
# sees the padding) to ONE length, ``pad_length`` of the longest request
# the traffic can ask for, the same at every seed: a checkout compiles
# the reference once, in its first run, and every later run finds it in
# the cache.
PAD_TO = 512


def pad_length(longest: int) -> int:
    """The length every request of a sample is padded to: ``longest``
    rounded up to a quarter of its power-of-two ceiling (at least
    ``PAD_TO``): coarse, so that it stays where it is when the longest
    request grows or shrinks by a few hundred tokens, and at most a
    third over it."""
    quantum = max(PAD_TO, (1 << max(0, int(longest) - 1).bit_length()) // 4)
    return -(-int(longest) // quantum) * quantum


def pick_sample(records: List[Dict[str, Any]], seed: int,
                count: int = SAMPLE_REQUESTS) -> List[Dict[str, Any]]:
    """``count`` of the greedy requests that finished with their tokens
    on record: the longest (prompt plus served tokens), and the others
    drawn from ``seed``."""
    done = [r for r in records
            if r.get("ok") and r.get("greedy") and r.get("tokens")
            and r.get("request") is not None]
    if not done:
        return []
    done.sort(key=lambda r: (r["prompt_tokens"] + len(r["tokens"]),
                             r["request"]))
    longest, rest = done[-1], done[:-1]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 17])
    take = min(max(0, count - 1), len(rest))
    picked = [rest[i] for i in sorted(rng.choice(len(rest), take,
                                                 replace=False))]
    return picked + [longest]


def request_gaps(reference, canonical, arch: Dict[str, Any],
                 quant: Dict[str, Any], prompt: Sequence[int],
                 tokens: Sequence[int], padded: int = 0) -> np.ndarray:
    """For each served token, how far its reference logit lies below
    the reference's best at that position, in standard deviations of
    the position's logits. The sequence is padded to ``padded`` (its own
    ``pad_length`` where that is more)."""
    seq = [int(x) for x in prompt] + [int(x) for x in tokens[:-1]]
    n = len(tokens)
    padded = max(int(padded), pad_length(len(seq)))
    # the head runs from a quarter of the padded length on, so that it
    # has four shapes at most whatever the prompt's length
    at = len(prompt) - 1
    quarter = max(PAD_TO, padded // 4)
    first = at // quarter * quarter
    lg = np.asarray(reference.all_logits(
        canonical, arch, quant, seq + [0] * (padded - len(seq)),
        first=first)[at - first:at - first + n], np.float64)
    chosen = lg[np.arange(n), np.asarray(tokens, np.int64)]
    return (lg.max(axis=-1) - chosen) / np.maximum(lg.std(axis=-1), 1e-30)


def next_token_gaps(reference, canonical, arch: Dict[str, Any],
                    quant: Dict[str, Any], sample: Dict[str, Any],
                    padded: int) -> Dict[str, List[float]]:
    """``served_gaps`` of a family whose step gives each sequence the
    tokens that follow its last one: ``request_gaps`` over the sample's
    ``prompt`` and ``tokens``, the first token's gap (the prefill's)
    apart from the later ones (decode steps'). ``sample["steps"]`` is
    not read: the served prefix is all the state such a token came
    from."""
    g = request_gaps(reference, canonical, arch, quant, sample["prompt"],
                     sample["tokens"], padded)
    return {"first": [float(g[0])], "later": [float(x) for x in g[1:]]}


def compare(reference, canonical, arch: Dict[str, Any],
            quant: Dict[str, Any], samples: List[Dict[str, Any]],
            longest: int = 0, gaps=None) -> Dict[str, Any]:
    """The numbers that decide, over ``samples`` (each with ``prompt``,
    ``tokens`` and, where the program said them, ``steps``): the widest
    gap of a first token (prefill), the widest and the mean gap of the
    later ones (decode). ``gaps`` is the configuration's ``served_gaps``
    (``harness/__init__.py`` has its contract; ``next_token_gaps``
    where none is given). ``longest`` is the longest request the
    traffic can ask for, prompt and answer: the one length comes from
    it, not from which requests a run happened to finish (a traced run
    whose longest request ended past the window compiled a second
    length: my chip run, PR 38)."""
    gaps = gaps or next_token_gaps
    first, later, seconds = [], [], []
    lengths = [len(s["prompt"]) + len(s["tokens"]) for s in samples]
    padded = pad_length(max(lengths + [int(longest), 1]))
    for s in samples:
        t = time.monotonic()
        g = gaps(reference, canonical, arch, quant, s, padded)
        seconds.append(round(time.monotonic() - t, 3))
        if len(g["first"]) + len(g["later"]) != len(s["tokens"]):
            raise ValueError(
                f"{gaps.__module__}.{gaps.__name__}: "
                f"{len(g['first'])} + {len(g['later'])} gaps for "
                f"{len(s['tokens'])} served tokens: every token has one")
        first.extend(float(x) for x in g["first"])
        later.extend(float(x) for x in g["later"])
    out: Dict[str, Any] = {
        "requests": len(samples),
        # what the reference's pass over each request cost, beside its
        # length: the longest part of a long-context cell's run
        "lengths": lengths,
        "padded": padded,
        "seconds": seconds,
        "served_tokens": len(first) + len(later),
        "longest": max(lengths, default=0),
        "reference_best_share": (
            sum(1 for x in first + later if x == 0.0)
            / max(1, len(first) + len(later))),
    }
    if first:
        out["prefill_gap_max"] = max(first)
    if later:
        out["decode_gap_max"] = max(later)
        out["decode_gap_mean"] = sum(later) / len(later)
    return out


def within(found: Dict[str, Any], limits: Dict[str, float]) -> bool:
    """True where every limited number was read and is inside its
    limit; a sample with nothing to compare is not correct."""
    return all(k in found and found[k] <= v for k, v in limits.items())
