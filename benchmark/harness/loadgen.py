"""The load generator: a process of its own that never imports jax or
the program (a chip belongs to one process, and a generator inside the
server's process would share its interpreter lock with the engine loop).

    python benchmark/harness/loadgen.py --port P --plan plan.json --out results.json

It builds the window's requests from the plan (traffic parameters, seed,
seconds, vocabulary), runs the warm-up, prints ``{"event": "warm"}``,
reads ``{"t0": <time.monotonic() of the window's start>}`` from its
standard input, sends the window's requests over HTTP to
``/v1/completions`` with ``stream=true``, follows each to its end (a
bounded drain after the window), repeats the probe request, writes the
records (with every request's streamed token ids) to ``--out`` and
prints ``{"event": "done"}``.

A record may say which step of the program committed each token: where
a stream event's ``choices[0]`` holds ``steps`` (integers, one a token
of that event) the record keeps them in ``steps``, parallel to
``tokens``; ``steps`` is None for a request none of whose events had
the key, and a request where only some had it has failed. What a number
means is the configuration's ``generation`` module's business.

Clock: ``time.monotonic()``, which on Linux is one clock for every
process of the machine.
"""

from __future__ import annotations

import argparse
import http.client
import json
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import traffic as traffic_mod  # noqa: E402

CONNECT_TIMEOUT_S = 10.0


def send_request(port: int, req: Dict[str, Any], deadline: float,
                 due: Optional[float] = None) -> Dict[str, Any]:
    """POST one streamed completion and record when each chunk came.

    ``deadline`` (monotonic) bounds the whole exchange: a request still
    open then counts as failed."""
    rec: Dict[str, Any] = {
        "due": due, "sent": None, "first": None, "chunks": [],
        "expected": req["max_tokens"], "received": 0, "ok": False,
        "error": None, "prompt_tokens": len(req["prompt"]),
        "tokens": [], "greedy": req["temperature"] == 0.0,
        "request": req.get("index"), "steps": None,
    }
    steps: List[int] = []
    body = json.dumps({
        "prompt": req["prompt"], "max_tokens": req["max_tokens"],
        "temperature": req["temperature"], "top_k": req["top_k"],
        "stream": True, "ignore_eos": True,
    })
    conn = None
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=CONNECT_TIMEOUT_S)
        rec["sent"] = time.monotonic()
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        sock = conn.sock      # the response may take the socket over
        sock.settimeout(max(0.1, deadline - time.monotonic()))
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"http {resp.status}"
            resp.read()
            return rec
        done = False
        while not done:
            left = deadline - time.monotonic()
            if left <= 0:
                rec["error"] = "not finished at the drain's end"
                return rec
            sock.settimeout(left)
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data:"):
                continue
            now = time.monotonic()
            payload = line[5:].strip()
            if payload == b"[DONE]":
                done = True
                break
            obj = json.loads(payload)
            if "choices" not in obj:
                rec["error"] = f"unexpected event {sorted(obj)}"
                return rec
            choice = obj["choices"][0]
            ids = (choice.get("text") or "").split()
            said = choice.get("steps")
            if said is not None:
                if not (isinstance(said, list) and len(said) == len(ids)
                        and all(type(x) is int for x in said)):
                    rec["error"] = (f"steps {said!r} beside {len(ids)} "
                                    "tokens: one integer a token")
                    return rec
                steps.extend(said)
            if ids:
                if rec["first"] is None:
                    rec["first"] = now
                rec["chunks"].append([now, len(ids)])
                rec["received"] += len(ids)
                rec["tokens"].extend(ids)
        if not done:
            rec["error"] = "stream closed without [DONE]"
        elif rec["received"] != rec["expected"]:
            rec["error"] = (f"{rec['received']} tokens, "
                            f"{rec['expected']} asked")
        elif steps and len(steps) != rec["received"]:
            rec["error"] = (f"steps beside {len(steps)} of "
                            f"{rec['received']} tokens: on every event "
                            "or on none")
        else:
            rec["ok"] = True
            rec["steps"] = steps or None
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if conn is not None:
            conn.close()
    return rec


def _run_together(port: int, reqs: List[Dict[str, Any]], deadline: float
                  ) -> List[Dict[str, Any]]:
    out: List[Optional[Dict[str, Any]]] = [None] * len(reqs)

    def one(i):
        out[i] = send_request(port, reqs[i], deadline)

    threads = [threading.Thread(target=one, args=(i,)) for i in
               range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out  # type: ignore[return-value]


def run_open(port: int, reqs: List[Dict[str, Any]], t0: float,
             seconds: float, drain: float) -> List[Dict[str, Any]]:
    """Send each request at ``t0 + due`` whatever the server does."""
    deadline = t0 + seconds + drain
    records: List[Optional[Dict[str, Any]]] = [None] * len(reqs)
    threads = []

    def one(i, due_abs):
        records[i] = send_request(port, reqs[i], deadline, due=due_abs)

    for i in sorted(range(len(reqs)), key=lambda j: reqs[j]["due"]):
        due_abs = t0 + reqs[i]["due"]
        wait = due_abs - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        t = threading.Thread(target=one, args=(i, due_abs))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    return records  # type: ignore[return-value]


def run_closed(port: int, clients: List[List[Dict[str, Any]]], t0: float,
               seconds: float, drain: float, stagger: float
               ) -> List[Dict[str, Any]]:
    """Each client sends its next request when the last one has ended,
    until the window closes; what is in flight then is followed to its
    end. Client c starts ``c * stagger`` seconds into the window, so
    that the order in which the first requests reach the server is the
    same in every run and not a race between threads."""
    t1 = t0 + seconds
    deadline = t1 + drain
    per_client: List[List[Dict[str, Any]]] = [[] for _ in clients]

    def client(c):
        wait = t0 + c * stagger - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        for req in clients[c]:
            if time.monotonic() >= t1:
                return
            rec = send_request(port, req, deadline)
            rec["client"] = c
            per_client[c].append(rec)
        # a client that runs out of requests inside the window is a
        # fault of the traffic file: too few requests_per_client
        if time.monotonic() < t1:
            per_client[c].append({
                "due": None, "sent": time.monotonic(), "first": None,
                "chunks": [], "expected": 0, "received": 0, "ok": False,
                "error": "client ran out of requests inside the window",
                "prompt_tokens": 0, "client": c, "tokens": [],
                "greedy": False, "request": None, "steps": None})

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(len(clients))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [r for recs in per_client for r in recs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.plan) as f:
        spec = json.load(f)
    traffic, seed = spec["traffic"], int(spec["seed"])
    seconds, vocab = float(spec["seconds"]), int(spec["vocab"])
    drain = float(traffic.get("drain_seconds", 30.0))
    plan = traffic_mod.window_plan(traffic, seed, seconds, vocab)
    waves = traffic_mod.warmup_plan(traffic, plan, seed, vocab)
    probe = traffic_mod.probe_request(plan, seed, vocab)
    # a record names its request by its place in the plan, so that the
    # runner, which can rebuild the plan, finds the prompt again
    for i, r in enumerate(traffic_mod.all_requests(plan)):
        r["index"] = i

    t_warm = time.monotonic()
    warm_deadline = t_warm + float(spec.get("warmup_limit_s", 900.0))
    warm_records = []
    for wave in waves:
        warm_records.extend(_run_together(args.port, wave, warm_deadline))
    probe_before = send_request(args.port, probe, warm_deadline)
    print(json.dumps({
        "event": "warm", "requests": len(warm_records),
        "failed": sum(not r["ok"] for r in warm_records),
        "errors": sorted({r["error"] for r in warm_records
                          if r["error"]})[:5],
        "seconds": time.monotonic() - t_warm}), flush=True)

    line = sys.stdin.readline()
    if not line:
        return 1
    t0 = float(json.loads(line)["t0"])
    if plan["kind"] == "open":
        records = run_open(args.port, plan["requests"], t0, seconds, drain)
    else:
        records = run_closed(args.port, plan["clients"], t0, seconds,
                             drain, float(traffic.get("client_stagger_s",
                                                      0.0)))
    t_end = time.monotonic()
    probe_after = send_request(args.port, probe, t_end + drain)
    with open(args.out, "w") as f:
        json.dump({
            "t0": t0, "seconds": seconds, "kind": plan["kind"],
            "records": records, "warmup": [
                {k: r[k] for k in ("ok", "error", "prompt_tokens",
                                   "expected")} for r in warm_records],
            "probe_before": probe_before, "probe_after": probe_after,
            "drained_s": t_end - (t0 + seconds),
        }, f)
    print(json.dumps({"event": "done"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
