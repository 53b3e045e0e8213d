"""Runner of the serving cells (traffic kinds ``open`` and ``closed``).

This process holds the chip. It builds the model on the device from the
seed, composes ``LLMEngine(EngineConfig(...))`` and ``OpenAIServer`` as
the program's ``api_server.main`` does (``from_pretrained`` is the one
step skipped) and listens on 127.0.0.1. The load comes from a child
process (``loadgen.py``) over real HTTP. End-to-end numbers are taken
from the child's records, on the client side; per-layer numbers from the
server's ``/metrics`` text at the window's edges and from a profiler
trace of a short stretch inside the window (``--trace 1`` only).

The configuration's ``reference``, ``weights``, ``costs`` and
``generation`` modules come from the cell (``cell.modules``); none is
imported here by name. ``correct`` rests on the reference twice, both
once the window has closed and the peak is read, on weights made anew
from the seed after the program's state is freed, and both through the
``generation`` module, which knows what one step of the family yields.
(1) The program's logits of one seeded sequence through a new cache of
the cell's kind, type and length (``program_rows``), a relative L2
name by name against the reference's one full forward pass
(``reference_rows``): the same names and shapes on both sides, and a
row of the prefill and one for every id that goes through the cache
after it at the least (``rows_fault``). It is what sees a cache of
lower precision or an accumulation in bfloat16. (2) The tokens the ENGINE streamed for a
sample of the window's own requests (``served.py``, ``served_gaps``):
its prefill programs, its decode step and its cache at the timed
batch. It is what sees a wrong row, page, position or token of the
timed path. The first is a forward pass of the check's own and costs
no set-up; the engine's own logits cannot be had without leaving the
resident decode step (a request with ``logprobs`` falls back to host
sampling).

The helpers below (``prefill_into_cache``, ``decode_through_cache``,
``reference_logits``) are what the default module,
``harness/generation.py``, is made of: the family's prefill of
``REF_PROMPT_TOKENS``, then ``DECODE_POSITIONS`` forced tokens one at a
time through that cache, the prefill's position and the decoded ones
apart.
"""

from __future__ import annotations

import contextlib
import json
import queue
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

from harness import common, promtext, served, stats
from harness import traffic as traffic_mod

STEP_SPAN = "engine_step"
REF_PROMPT_TOKENS = 32
DECODE_POSITIONS = 8
BROWNOUT = "bigdl_tpu_brownout_level"
LOADGEN = Path(__file__).resolve().parent / "loadgen.py"


def check_ids(seed: int, vocab: int,
              n: int = REF_PROMPT_TOKENS + DECODE_POSITIONS):
    """The seeded sequence of the logits comparison: a prompt of
    ``REF_PROMPT_TOKENS`` and ``DECODE_POSITIONS`` forced tokens, or the
    ``n`` ids of a generation module that states its own lengths
    (``sequence_of``)."""
    import numpy as np

    return np.random.default_rng([seed & 0xFFFFFFFF, 13]).integers(
        1, vocab, n)


def sequence_of(generation) -> Tuple[int, int]:
    """How many ids of the seeded sequence are the prompt and how many
    then go through the cache: the module's ``SEQUENCE = (prompt,
    later)`` where it has one (a family that prefills whole blocks and
    then steps a whole block needs a multiple of its block), else
    ``REF_PROMPT_TOKENS`` and ``DECODE_POSITIONS``. A module may
    lengthen either part and shorten neither."""
    prompt, later = getattr(generation, "SEQUENCE",
                            (REF_PROMPT_TOKENS, DECODE_POSITIONS))
    if not (type(prompt) is int and type(later) is int
            and prompt >= REF_PROMPT_TOKENS and later >= DECODE_POSITIONS):
        raise ValueError(
            f"{getattr(generation, '__name__', generation)}.SEQUENCE = "
            f"{(prompt, later)!r}: two "
            f"whole numbers, at least {REF_PROMPT_TOKENS} ids of prompt "
            f"and {DECODE_POSITIONS} after it")
    return prompt, later


def prefill_into_cache(model, eng_cfg: Dict[str, Any], prompt, seed: int):
    """The program's prefill of ``prompt`` (one row) into a new cache of
    the kind, type and length the cell's engine holds: a slab of
    ``max_seq`` positions, or pages of ``kv_page_size`` behind a block
    table in an order drawn from the seed. Returns the last position's
    logits and the state ``decode_through_cache`` goes on from."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    family, cfg = model.family, model.config
    kv = eng_cfg.get("kv_cache_dtype", "bf16")
    max_seq = int(eng_cfg["max_seq"])
    page = int(eng_cfg.get("kv_page_size", 0) or 0)
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    if page:
        n_pages = max_seq // page
        cache = family.new_paged_cache(cfg, n_pages + 1, page, 1, kv)
        table = jnp.asarray(1 + np.random.default_rng(
            [seed & 0xFFFFFFFF, 19]).permutation(n_pages), jnp.int32)[None]
        fwd = jax.jit(family.forward_paged, static_argnums=1)
        step = lambda t, c: fwd(model.params, cfg, t, c, table)  # noqa: E731
    else:
        cache = family.new_cache(cfg, 1, max_seq, kv)
        fwd = jax.jit(family.forward, static_argnums=1)
        step = lambda t, c: fwd(model.params, cfg, t, c)  # noqa: E731
    lg, cache = step(toks, cache)
    return np.asarray(lg[0, -1], np.float32), (step, cache)


def decode_through_cache(state, forced):
    """Logits ``[len(forced), V]`` of ``forced`` fed one token at a
    time through the cache ``prefill_into_cache`` filled."""
    import jax.numpy as jnp
    import numpy as np

    step, cache = state
    rows = []
    for t in forced:
        lg, cache = step(jnp.asarray([[int(t)]], jnp.int32), cache)
        rows.append(np.asarray(lg[0, -1], np.float32))
    return np.stack(rows)


def reference_logits(reference, canonical, arch, quant, ids,
                     n_prompt: int) -> Dict[str, Any]:
    """The reference's ONE full forward pass over ``ids``, as the rows
    ``prefill_into_cache`` over the first ``n_prompt`` and
    ``decode_through_cache`` over the rest give: the prompt's last
    position, and every position after it."""
    import numpy as np

    ref = np.asarray(reference.all_logits(
        canonical, arch, quant, [int(x) for x in ids], first=n_prompt - 1))
    return {"prefill": ref[0], "decode": ref[1:]}


def _shapes(rows: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    import numpy as np

    return {k: tuple(np.shape(v)) for k, v in rows.items()}


def rows_errors(program: Dict[str, Any], reference: Dict[str, Any]
                ) -> Dict[str, Optional[float]]:
    """Relative L2 of the program's rows from the reference's, name by
    name; None under a name that only one side has, or whose rows the
    two sides shape differently."""
    ps, rs = _shapes(program), _shapes(reference)
    names = list(program) + [k for k in reference if k not in program]
    return {k: (common.relative_l2(program[k], reference[k])
                if k in ps and ps[k] == rs.get(k) else None)
            for k in names}


def rows_fault(program: Dict[str, Any], reference: Dict[str, Any],
               owed: int) -> Optional[str]:
    """What keeps the two sides' rows from being the comparison the
    contract asks for, in one line; None where nothing does. The names
    and each name's shape are the same on both sides, and the rows of
    all names together (a name's first dimension, 1 for a single row)
    are ``owed`` or more: one of the prefill and one for every id that
    goes through the cache after it, so that a module cannot be
    ``correct`` on an easy row alone."""
    ps, rs = _shapes(program), _shapes(reference)
    if set(ps) != set(rs):
        return (f"generation rows named apart: program_rows {sorted(ps)}, "
                f"reference_rows {sorted(rs)}")
    apart = {k: (ps[k], rs[k]) for k in ps if ps[k] != rs[k]}
    if apart:
        return f"generation rows shaped apart (program, reference): {apart}"
    rows = sum(shape[0] if len(shape) > 1 else 1 for shape in ps.values())
    if rows < owed:
        return (f"generation rows compared: {rows} in {ps}, {owed} owed "
                f"(one of the prefill, one for every id after the prompt)")
    return None


def logits_errors(reference, canonical, arch, quant, ids, prefill_row,
                  decode_rows) -> Dict[str, float]:
    """Relative L2 of the program's logits from the reference's ONE full
    forward pass over ``ids``: the prefill's position, and the decoded
    positions together."""
    return rows_errors(
        {"prefill": prefill_row, "decode": decode_rows},
        reference_logits(reference, canonical, arch, quant, ids,
                         len(ids) - len(decode_rows)))


def _gauge(registry, name: str) -> float:
    """One gauge of the program's registry, read without rendering the
    rest: cheap enough for every second of the window."""
    return float(sum(child.value for fam in registry.families()
                     if fam.name == name for _, child in fam.children()))


class _ChildLines:
    """The child's standard output, line by line, with a time limit."""

    def __init__(self, proc):
        self.q: "queue.Queue[Optional[str]]" = queue.Queue()
        self.t = threading.Thread(target=self._pump, args=(proc,),
                                  daemon=True)
        self.t.start()

    def _pump(self, proc):
        for line in proc.stdout:
            self.q.put(line)
        self.q.put(None)

    def event(self, name: str, timeout: float) -> Dict[str, Any]:
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise RuntimeError(f"load generator: no {name!r} event "
                                   f"within {timeout:.0f} s")
            try:
                line = self.q.get(timeout=left)
            except queue.Empty:
                continue
            if line is None:
                raise RuntimeError(f"load generator ended before "
                                   f"{name!r}")
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if obj.get("event") == name:
                return obj


def _start_loadgen(port: int, traffic, seed: int, seconds: float,
                   vocab: int, out_dir: Path, tag: str, err_file):
    """Start the generator on ``traffic`` and wait until its warm-up is
    done. Returns the child, its line reader, the warm-up's report and
    the path its records will be written to."""
    plan_path = out_dir / f"{tag}plan.json"
    results_path = out_dir / f"{tag}records.json"
    with open(plan_path, "w") as f:
        json.dump({"traffic": traffic, "seed": seed, "seconds": seconds,
                   "vocab": vocab}, f)
    child = subprocess.Popen(
        [sys.executable, str(LOADGEN), "--port", str(port),
         "--plan", str(plan_path), "--out", str(results_path)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=err_file, text=True)
    lines = _ChildLines(child)
    try:
        warm = lines.event("warm", timeout=1100.0)
    except BaseException:
        _stop(child)
        raise
    return child, lines, warm, results_path


def _open_window(child) -> float:
    """Tell the generator when the window starts; returns that time."""
    t0 = time.monotonic() + 0.25
    child.stdin.write(json.dumps({"t0": t0}) + "\n")
    child.stdin.flush()
    return t0


def _records(child, lines, traffic, results_path: Path) -> Dict[str, Any]:
    """Wait for the generator's end (the window's drain) and read what
    it recorded."""
    drain = float(traffic.get("drain_seconds", 30.0))
    lines.event("done", timeout=drain * 2 + 60.0)
    child.wait(timeout=30.0)
    with open(results_path) as f:
        return json.load(f)


def _stop(child) -> None:
    if child is not None and child.poll() is None:
        child.kill()
        child.wait()


def _sweep(engine, port: int, traffic, rates, seed: int, seconds: float,
           vocab: int, out_dir: Path, err_file) -> None:
    """The builder's one-off search for the knee: the same server, one
    window per rate, each printed on a line of its own. The backlog at
    a window's end (queue depth, and how long the drain took) and the
    failures say whether the rate was sustained."""
    import copy

    for i, rate in enumerate(rates):
        tr = copy.deepcopy(traffic)
        tr["arrivals"]["rate_rps"] = float(rate)
        child, lines, _, results_path = _start_loadgen(
            port, tr, seed + i, seconds, vocab, out_dir, f"sweep{i}_",
            err_file)
        try:
            t0 = _open_window(child)
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
            snap = promtext.parse(engine.registry.render())
            res = _records(child, lines, tr, results_path)
        finally:
            _stop(child)
        m = stats.serving_metrics(res["records"], res["t0"], seconds)
        common.note(
            info="sweep", rate_rps=rate, attempted=m["attempted"],
            failed=m["failed"], ttft_p50_ms=m["ttft_p50_ms"],
            ttft_p90_ms=m["ttft_p90_ms"], itl_p95_ms=m["itl_p95_ms"],
            output_tokens_per_s=m["output_tokens_per_s"],
            queue_depth_at_end=promtext.total(snap, "bigdl_tpu_queue_depth"),
            slots_at_end=promtext.total(snap, "bigdl_tpu_slot_occupancy"),
            drained_s=res["drained_s"], late_p99_ms=m["late_p99_ms"])


def _reference_checks(cell, model, records, seed: int, seconds: float,
                      dims, clock: common.WallClock) -> Dict[str, Any]:
    """Both comparisons with the reference, once the window has closed
    and the peak is read, each through the configuration's
    ``generation`` module: the program's logits of one seeded sequence
    through a cache of the cell's kind; then the program's state goes
    and the reference gets the device to itself, on weights made anew
    from the seed ONCE, for that sequence, for the configuration's own
    layer check where it has one, and for what the window served.
    ``clock`` is charged each part."""
    t_check = time.monotonic()
    config, traffic = cell.config, cell.traffic
    reference, weights = cell.modules["reference"], cell.modules["weights"]
    generation = cell.modules["generation"]
    kv_dtype = config["engine"].get("kv_cache_dtype", "bf16")
    n_prompt, n_later = sequence_of(generation)
    ids = check_ids(seed, dims.vocab_size, n_prompt + n_later)
    program = generation.program_rows(model, config["engine"], ids, seed)
    common.free_device()
    clock.lap("check_a_program")
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    canonical = weights.canonical_params(config, seed)
    clock.lap("canonical_tree")
    # a configuration's own check runs inside ``canonical_params`` and
    # leaves its seconds and its compared numbers on the tree
    own = canonical.get("layer_check") or {}
    clock.move(own.get("seconds", 0.0), "canonical_tree", "layer_check")
    expected = generation.reference_rows(
        reference, canonical, config["reference"], quant, ids)
    rel = rows_errors(program, expected)
    clock.lap("check_a_reference")
    plan = traffic_mod.all_requests(traffic_mod.window_plan(
        traffic, seed, seconds, dims.vocab_size))
    # ``steps``: which step committed each token, where the program's
    # stream said so (None where it did not); what a number means is
    # the generation module's and its family's business
    samples = [{"prompt": plan[r["request"]]["prompt"],
                "tokens": [int(x) for x in r["tokens"]],
                "steps": r.get("steps")}
               for r in served.pick_sample(records, seed)]
    found = served.compare(
        reference, canonical, config["reference"], quant, samples,
        longest=max(len(p["prompt"]) + int(p["max_tokens"]) for p in plan),
        gaps=generation.served_gaps)
    del canonical
    common.free_device()
    clock.lap("check_b_served")
    return {"rel": rel, "tolerance": reference.tolerance(config, kv_dtype),
            # rows that are not the comparison the contract asks for:
            # the run says why and is not ``correct``
            "rows_fault": rows_fault(program, expected, n_later + 1),
            "served": found,
            "limits": reference.served_gap_limits(config, kv_dtype),
            "own_compared": list(own.get("compared", [])),
            "own_within": bool(own.get("within", True)),
            "seconds": time.monotonic() - t_check}


def run(cell, *, seed: int, seconds: float, trace: bool, tiny: bool,
        t_process: float, out_dir: Path, device: Dict[str, Any],
        peaks: Optional[Dict[str, float]],
        sweep_rates: Optional[list] = None) -> Dict[str, Any]:
    import jax

    watch = common.CompileWatch().install()
    clock = common.WallClock(t_process)
    clock.lap("devices_ready")
    marks = {"devices_ready_s": clock.phases["devices_ready"]}
    config, traffic = cell.config, cell.traffic
    weights, costs = cell.modules["weights"], cell.modules["costs"]
    eng_cfg = dict(config["engine"])
    kv_dtype = eng_cfg.get("kv_cache_dtype", "bf16")
    dims = costs.Dims.from_config(config)

    from bigdl_tpu.serving.api_server import OpenAIServer
    from bigdl_tpu.serving.engine import EngineConfig, LLMEngine

    t_build = time.monotonic()
    model, build_stages = weights.build_model(config, seed, merge=True)
    build_s = time.monotonic() - t_build
    clock.lap("weights")

    overload = eng_cfg.pop("overload", None)
    if overload is not None:
        from bigdl_tpu.serving.overload import OverloadConfig

        eng_cfg["overload"] = OverloadConfig(**overload)
    engine = LLMEngine(model, EngineConfig(prefix_cache_entries=0,
                                           **eng_cfg))
    # The step is wrapped in every run, traced or not, and the span is
    # a null context when not: a program that holds a Pallas kernel is
    # keyed in the compile cache by the call stack it was traced from,
    # so a wrapper in traced runs only made each side's first traced run
    # compile both decode programs again (set-up 147 s for 99 s in the
    # sparse-latent cell: my chip run, PR 38).
    inner = engine.step
    span = (jax.profiler.TraceAnnotation if trace
            else lambda _name: contextlib.nullcontext())

    def wrapped_step():
        with span(STEP_SPAN):
            return inner()

    engine.step = wrapped_step
    marks["model_and_engine_s"] = time.monotonic() - t_process
    server = OpenAIServer(engine, None)
    httpd = server.serve("127.0.0.1", 0, background=True)
    marks["serving_s"] = time.monotonic() - t_process
    port = httpd.server_address[1]
    clock.lap("engine_and_server")
    child = None
    err_file = open(out_dir / "loadgen.stderr", "w")
    try:
        if sweep_rates:
            _sweep(engine, port, traffic, sweep_rates, seed, seconds,
                   dims.vocab_size, out_dir, err_file)
        child, lines, warm, results_path = _start_loadgen(
            port, traffic, seed, seconds, dims.vocab_size, out_dir, "",
            err_file)

        def counters():
            return promtext.parse(engine.registry.render())

        c_setup = watch.snapshot()
        clock.lap("warmup")
        t0 = _open_window(child)
        setup_s = t0 - t_process
        snap0 = counters()
        w0 = watch.snapshot()

        trace_dir = out_dir / "trace"
        trace_ab = None
        occupancy = []
        brownout_seen = [_gauge(engine.registry, BROWNOUT)]
        if trace:
            tr_start = min(float(traffic.get("trace_start_s", 6.0)),
                           seconds * 0.4)
            tr_len = min(float(traffic.get("trace_seconds", 3.0)),
                         seconds * 0.4)
            time.sleep(max(0.0, t0 + tr_start - time.monotonic()))
            common.start_trace(trace_dir)
            a = time.monotonic()
            time.sleep(tr_len)
            b = time.monotonic()
            jax.profiler.stop_trace()
            trace_ab = (a, b)
        while time.monotonic() < t0 + seconds:
            # once a second through the window: a brownout that engaged
            # and recovered inside it fails the run
            brownout_seen.append(_gauge(engine.registry, BROWNOUT))
            if trace:
                v = promtext.total(counters(), "bigdl_tpu_slot_occupancy")
                if v is not None:
                    occupancy.append(v)
            time.sleep(min(1.0, max(0.0, t0 + seconds - time.monotonic())))
        snap1 = counters()
        w1 = watch.snapshot()
        mem_peak = common.memory_peak_bytes()
        clock.lap("window")

        res = _records(child, lines, traffic, results_path)
        snap2 = counters()
        stats_json = engine.stats_snapshot()
    finally:
        _stop(child)
        err_file.close()
        server.shutdown()
        httpd.server_close()

    records = res["records"]
    m = stats.serving_metrics(records, res["t0"], seconds)
    del engine, server, httpd      # the engine's cache may go at once
    clock.lap("drain")
    ref = _reference_checks(cell, model, records, seed, seconds, dims,
                            clock)
    wrong_length = sum(1 for r in records
                       if r.get("error") and "asked" in r["error"])
    tracked_compiles = promtext.delta(snap0, snap1,
                                      "bigdl_tpu_jit_compiles_total") or 0.0
    jax_compiles = watch.programs_between(w0, w1)
    quarantined = promtext.total(
        snap2, "bigdl_tpu_requests_quarantined_total") or 0.0
    retries = promtext.total(snap2, "bigdl_tpu_step_retries_total") or 0.0
    fallbacks = promtext.total(snap2, "bigdl_tpu_kernel_probe_total",
                               {"outcome": "fallback"}) or 0.0
    brownout = max(brownout_seen + [
        promtext.total(s, BROWNOUT) or 0.0 for s in (snap0, snap1, snap2)])
    shed = promtext.total(snap2, "bigdl_tpu_requests_shed_total") or 0.0
    preempted = promtext.delta(snap0, snap2,
                               "bigdl_tpu_preemptions_total") or 0.0
    pb, pa = res["probe_before"], res["probe_after"]
    checks = {
        "warmup_all_ok": warm["failed"] == 0,
        "exact_token_counts": wrong_length == 0,
        "no_compile_in_window": tracked_compiles == 0 and jax_compiles == 0,
        "no_quarantine": quarantined == 0,
        "no_step_retry": retries == 0,
        "no_fallback_probe": fallbacks == 0,
        "no_brownout_no_shed": brownout == 0 and shed == 0,
        "probe_repeats": bool(pb["ok"] and pa["ok"]
                              and pb["tokens"] == pa["tokens"]),
        "reference_within_tolerance": ref["rows_fault"] is None and all(
            v is not None and v <= ref["tolerance"]
            for v in ref["rel"].values()),
        "served_tokens_within_reference_gap":
            served.within(ref["served"], ref["limits"]),
        "configuration_layer_check": ref["own_within"],
    }
    values = dict(m)
    values["setup_s"] = setup_s
    dev = dict(device)
    dev["memory_peak_bytes"] = mem_peak
    result: Dict[str, Any] = {
        "correct": all(checks.values()),
        "attempted": m["attempted"], "failed": m["failed"], "device": dev,
    }
    if not trace:
        # a CPU run yields no time and no rate: counts only
        result["metrics"] = common.select_end_to_end(
            cell, {} if tiny else values)
    else:
        work = costs.serving_work(config, dims, records, kv_dtype, trace_ab)
        obs = {
            "counters_start": snap0, "counters_end": snap1, "client": m,
            "memory_peak_bytes": mem_peak or None,
            "device_kind": device["kind"] if not tiny else None,
            "peaks": peaks, "work": work,
        }
        common.traced_metrics(
            cell, result, obs, trace_dir if trace_ab is not None else None,
            STEP_SPAN, tiny, out_dir,
            slot_occupancy_mean=(sum(occupancy) / len(occupancy)
                                 if occupancy else None), work=work)
        clock.lap("trace_reduction")

    wall = clock.close()
    common.note(
        info="run", workload=cell.name, seed=seed, seconds=seconds,
        checks=checks, reference_rel_l2=ref["rel"],
        reference_tolerance=ref["tolerance"],
        served=dict(ref["served"], limits=ref["limits"]),
        reference_checks_s=ref["seconds"],
        wall_s=wall["wall_s"], budget_s=common.RUN_BUDGET_S,
        phases=wall["phases"],
        samples={"ttft": m["n_ttft"], "gaps": m["n_gaps"],
                 "tokens_in_window": m["tokens_in_window"],
                 "highest_ttft_percentile_with_10_beyond":
                     stats.highest_supported_percentile(m["n_ttft"])},
        client={k: m[k] for k in ("ttft_mean_ms", "ttft_p50_ms",
                                  "ttft_p90_ms", "ttft_ms",
                                  "itl_p50_ms", "itl_p95_ms",
                                  "output_tokens_per_s")},
        generator_late_ms={"p50": m["late_p50_ms"], "p99": m["late_p99_ms"]},
        warmup={"requests": warm["requests"], "failed": warm["failed"],
                "seconds": warm["seconds"], "errors": warm["errors"]},
        setup={"build_s": build_s, "setup_s": setup_s,
               "marks": marks,
               "build_stages": build_stages,
               "backend_compiles": c_setup["backend_compiles"],
               "cache_hits": c_setup["cache_hits"],
               "backend_compile_s": c_setup["backend_seconds"]},
        window_compiles={"tracked": tracked_compiles, "jax": jax_compiles},
        drained_s=res["drained_s"], preemptions=preempted,
        errors=sorted({r["error"] for r in records if r["error"]})[:5],
        compile_table={
            k: {"compiles": v.get("compiles"), "total_s": v.get("total_s")}
            for k, v in (stats_json.get("compile_table") or {}).items()
            if v.get("compiles")})

    if ref["rows_fault"]:
        print(ref["rows_fault"], file=sys.stderr, flush=True)
    result["compared"] = common.report_compared(
        ref["own_compared"]
        + [(f"reference_rel_l2.{k}", v, ref["tolerance"])
           for k, v in ref["rel"].items()]
        + [(k, ref["served"].get(k), v) for k, v in ref["limits"].items()],
        checks)
    return result
