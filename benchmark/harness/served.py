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
module is the cell's.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

SAMPLE_REQUESTS = 4
# The reference compiles one program per sequence length, and a sample
# drawn from the seed has new lengths in every run: sequences are padded
# at their END to a multiple of PAD_TO (causal attention: no compared
# position sees the padding), so that a checkout compiles a handful of
# lengths once and every later run finds them in the cache.
PAD_TO = 512


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
                 tokens: Sequence[int]) -> np.ndarray:
    """For each served token, how far its reference logit lies below
    the reference's best at that position, in standard deviations of
    the position's logits."""
    seq = [int(x) for x in prompt] + [int(x) for x in tokens[:-1]]
    n = len(tokens)
    padded = -(-len(seq) // PAD_TO) * PAD_TO
    # as many rows of logits as hold the served positions wherever the
    # padding puts them: a whole number of PAD_TO, the same for every
    # answer of up to PAD_TO tokens
    first = max(0, padded - PAD_TO * (1 + -(-n // PAD_TO)))
    lg = np.asarray(reference.all_logits(
        canonical, arch, quant, seq + [0] * (padded - len(seq)),
        first=first), np.float64)
    at = len(prompt) - 1 - first
    lg = lg[at:at + n]
    chosen = lg[np.arange(n), np.asarray(tokens, np.int64)]
    return (lg.max(axis=-1) - chosen) / np.maximum(lg.std(axis=-1), 1e-30)


def compare(reference, canonical, arch: Dict[str, Any],
            quant: Dict[str, Any], samples: List[Dict[str, Any]]
            ) -> Dict[str, Any]:
    """The numbers that decide, over ``samples`` (each with ``prompt``
    and ``tokens``): the widest gap of a first token (prefill), the
    widest and the mean gap of the later ones (decode)."""
    first, later = [], []
    for s in samples:
        g = request_gaps(reference, canonical, arch, quant, s["prompt"],
                         s["tokens"])
        first.append(float(g[0]))
        later.extend(float(x) for x in g[1:])
    out: Dict[str, Any] = {
        "requests": len(samples),
        "served_tokens": len(first) + len(later),
        "longest": max((len(s["prompt"]) + len(s["tokens"])
                        for s in samples), default=0),
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
