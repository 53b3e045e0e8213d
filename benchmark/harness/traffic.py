"""One general traffic generator: a traffic file of parameters plus a
seed gives the requests of a window. NumPy and the standard library
only: the load generator imports this and must never import jax.

Every seed gets the SAME requests at the SAME times: the sizes and the
arrival gaps are the mid-quantiles of the file's distributions (so the
amount of work is fixed) in an order drawn from the file's own
``order_seed``. The run's seed decides the token values (and, in the
runner, the weights), never a shape or a time. Near its knee a server's
tail is decided by the order in which a window's few dozen requests
come: with the order drawn from the run's seed the same code read a
90th-percentile time to first token from 1.6 s to 3.5 s (six seeds, my
chip run, PR 24), while two runs of one order agreed within 3 %.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np


def _quantile(dist: Dict[str, Any], u: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "uniform":
        v = dist["min"] + (dist["max"] - dist["min"]) * u
    elif kind == "exponential":
        v = -np.log1p(-u) * float(dist.get("mean", 1.0))
    elif kind == "gamma":
        # mean 1, coefficient of variation cv; quantiles by sorting a
        # large fixed-seed sample (no scipy here)
        cv = float(dist["cv"])
        shape = 1.0 / (cv * cv)
        ref = np.sort(np.random.default_rng(12345).gamma(
            shape, 1.0 / shape, 200_000))
        v = ref[np.minimum((u * len(ref)).astype(int), len(ref) - 1)]
        v = v * float(dist.get("mean", 1.0))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist and kind != "uniform":
        v = np.maximum(v, dist["min"])
    if "max" in dist and kind != "uniform":
        v = np.minimum(v, dist["max"])
    return v


def population(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole numbers at the mid-quantiles of ``dist``, clipped to
    its ``min``/``max`` and rounded down to ``multiple_of``."""
    u = (np.arange(n) + 0.5) / n
    v = _quantile(dist, u)
    m = int(dist.get("multiple_of", 1))
    v = (np.floor(v / m) * m).astype(np.int64)
    if "min" in dist:
        v = np.maximum(v, int(dist["min"]))
    return v


def _apportion(weights: np.ndarray, n: int) -> np.ndarray:
    """``n`` split among ``weights`` by largest remainder."""
    w = np.asarray(weights, float)
    exact = w / w.sum() * n
    base = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - base))[: n - base.sum()]:
        base[i] += 1
    return base


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, *salt])


def document_lengths(docs: Dict[str, Any]) -> np.ndarray:
    return population(docs["tokens"], int(docs["count"]))


def document_tokens(docs: Dict[str, Any], seed: int, vocab: int
                    ) -> List[np.ndarray]:
    lens = document_lengths(docs)
    return [_rng(seed, 7, i).integers(1, vocab, int(n)).astype(np.int64)
            for i, n in enumerate(lens)]


def _order_seed(traffic: Dict[str, Any]) -> int:
    return int(traffic.get("order_seed", 0))


def _request_set(traffic: Dict[str, Any], n: int, salt: int
                 ) -> List[Dict[str, Any]]:
    """``n`` requests (no tokens yet): private prompt length, output
    length, sampling, document index, each a fixed population in the
    order the file's ``order_seed`` picks."""
    rng = _rng(_order_seed(traffic), 1, salt)
    plen = rng.permutation(population(traffic["prompt_tokens"], n))
    olen = rng.permutation(population(traffic["output_tokens"], n))
    counts = _apportion(np.array([s["share"] for s in traffic["sampling"]]),
                        n)
    samp = rng.permutation(np.repeat(np.arange(len(counts)), counts))
    docs = traffic.get("documents")
    if docs:
        ranks = np.arange(1, int(docs["count"]) + 1, dtype=float)
        pop = docs["popularity"]
        if pop["dist"] == "zipf":
            w = ranks ** -float(pop["s"])
        elif pop["dist"] == "uniform":
            w = np.ones_like(ranks)
        else:
            raise ValueError(f"unknown popularity {pop['dist']!r}")
        dcount = _apportion(w, n)
        doc = rng.permutation(np.repeat(np.arange(len(dcount)), dcount))
    else:
        doc = np.full(n, -1)
    out = []
    for i in range(n):
        s = traffic["sampling"][int(samp[i])]
        out.append({"prompt_len": int(plen[i]), "max_tokens": int(olen[i]),
                    "temperature": float(s.get("temperature", 0.0)),
                    "top_k": int(s.get("top_k", 0)),
                    "doc": int(doc[i])})
    return out


def arrival_times(arrivals: Dict[str, Any], n: int, seconds: float,
                  order_seed: int) -> np.ndarray:
    """``n`` due times in ``[0, seconds)``: the mid-quantiles of the
    gap distribution, scaled to fill the window, in the file's order."""
    proc = arrivals["process"]
    if proc == "poisson":
        dist = {"dist": "exponential"}
    elif proc == "gamma":
        dist = {"dist": "gamma", "cv": arrivals["cv"]}
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    gaps = _quantile(dist, (np.arange(n) + 0.5) / n)
    gaps = _rng(order_seed, 2).permutation(gaps)
    gaps = gaps / gaps.sum() * seconds
    due = np.cumsum(gaps) - gaps[0]
    return due


def open_loop_count(traffic: Dict[str, Any], seconds: float) -> int:
    return max(1, int(round(float(traffic["arrivals"]["rate_rps"])
                            * seconds)))


def fill_tokens(reqs: List[Dict[str, Any]], seed: int, vocab: int,
                salt: int, docs: Optional[List[np.ndarray]]) -> None:
    for i, r in enumerate(reqs):
        private = _rng(seed, 3, salt, i).integers(
            1, vocab, r["prompt_len"]).astype(np.int64)
        if r["doc"] >= 0:
            ids = np.concatenate([docs[r["doc"]], private])
        else:
            ids = private
        r["prompt"] = [int(x) for x in ids]


def window_plan(traffic: Dict[str, Any], seed: int, seconds: float,
                vocab: int) -> Dict[str, Any]:
    """The requests of one window.

    open:   ``{"kind", "requests": [... "due" ...]}``
    closed: ``{"kind", "clients": [[requests of client 0], ...]}``; each
            client sends its list in order, as far as the window lasts.
    """
    docs_spec = traffic.get("documents")
    docs = document_tokens(docs_spec, seed, vocab) if docs_spec else None
    kind = traffic["kind"]
    if kind == "open":
        n = open_loop_count(traffic, seconds)
        reqs = _request_set(traffic, n, 0)
        due = arrival_times(traffic["arrivals"], n, seconds,
                            _order_seed(traffic))
        for r, t in zip(reqs, due):
            r["due"] = float(t)
        fill_tokens(reqs, seed, vocab, 0, docs)
        return {"kind": kind, "requests": reqs, "documents": docs}
    if kind == "closed":
        per = int(traffic["requests_per_client"])
        clients = []
        for c in range(int(traffic["clients"])):
            reqs = _request_set(traffic, per, 100 + c)
            fill_tokens(reqs, seed, vocab, 100 + c, docs)
            clients.append(reqs)
        return {"kind": kind, "clients": clients, "documents": docs}
    raise ValueError(f"traffic kind {kind!r} has no request plan")


def all_requests(plan: Dict[str, Any]) -> List[Dict[str, Any]]:
    return (plan["requests"] if plan["kind"] == "open"
            else [r for c in plan["clients"] for r in c])


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, n))))


def warmup_plan(traffic: Dict[str, Any], plan: Dict[str, Any], seed: int,
                vocab: int) -> List[List[Dict[str, Any]]]:
    """Waves of warm-up requests that drive every shape the window will
    use; the requests of one wave go out together.

    1. every document once, with a short question (fills the prefix
       cache, as the traffic file's ``warm_documents`` asks);
    2. one greedy request for each class of prompt the window holds: the
       longest of each (document length, power-of-two ceiling of the
       whole prompt) pair, one at a time;
    3. one request of each sampling variant alone, then all variants
       together, so that every decode and sampler variant is compiled.
    """
    all_reqs = all_requests(plan)
    docs = plan.get("documents")
    waves: List[List[Dict[str, Any]]] = []
    rng = _rng(seed, 9)

    def make(doc: int, private_len: int, max_tokens: int, samp) -> Dict:
        private = rng.integers(1, vocab, private_len).astype(np.int64)
        ids = (np.concatenate([docs[doc], private]) if doc >= 0
               else private)
        return {"prompt": [int(x) for x in ids], "prompt_len": private_len,
                "doc": doc, "max_tokens": max_tokens,
                "temperature": float(samp.get("temperature", 0.0)),
                "top_k": int(samp.get("top_k", 0))}

    greedy = {"temperature": 0.0, "top_k": 0}
    if docs and traffic["documents"].get("warm_documents", True):
        min_q = int(min(r["prompt_len"] for r in all_reqs))
        for d in range(len(docs)):
            waves.append([make(d, min_q, 2, greedy)])
    classes: Dict[Any, Dict[str, Any]] = {}
    for r in all_reqs:
        dlen = len(docs[r["doc"]]) if r["doc"] >= 0 else 0
        key = (dlen, _pow2_ceil(dlen + r["prompt_len"]))
        if key not in classes or r["prompt_len"] > \
                classes[key]["prompt_len"]:
            classes[key] = r
    for key in sorted(classes):
        r = classes[key]
        waves.append([make(r["doc"], r["prompt_len"], 2, greedy)])
    short = int(min(r["prompt_len"] for r in all_reqs))
    ref = min(all_reqs, key=lambda r: r["prompt_len"])
    variants = traffic["sampling"]
    for s in variants:
        waves.append([make(ref["doc"], short, 4, s)])
    if len(variants) > 1:
        waves.append([make(ref["doc"], short, 4, s) for s in variants])
    return waves


def probe_request(plan: Dict[str, Any], seed: int, vocab: int
                  ) -> Dict[str, Any]:
    """One fixed greedy request, sent in warm-up and again after the
    window: its tokens must be byte-identical."""
    ref = min(all_requests(plan), key=lambda r: r["prompt_len"])
    docs = plan.get("documents")
    private = _rng(seed, 11).integers(1, vocab, ref["prompt_len"]) \
        .astype(np.int64)
    ids = (np.concatenate([docs[ref["doc"]], private]) if ref["doc"] >= 0
           else private)
    return {"prompt": [int(x) for x in ids], "prompt_len": ref["prompt_len"],
            "doc": ref["doc"], "max_tokens": 8, "temperature": 0.0,
            "top_k": 0}


def train_batch(traffic: Dict[str, Any], seed: int, vocab: int
                ) -> Dict[str, np.ndarray]:
    """The one fixed micro-batch of a training cell: tokens from the
    seed, full mask."""
    b, s = int(traffic["micro_batch"]), int(traffic["seq_len"])
    ids = _rng(seed, 5).integers(1, vocab, (b, s)).astype(np.int32)
    return {"input_ids": ids, "attention_mask": np.ones((b, s), np.int32)}
