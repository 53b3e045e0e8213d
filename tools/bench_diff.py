#!/usr/bin/env python3
"""Compare two BENCH_*.json files and flag regressions.

Usage:
    python tools/bench_diff.py OLD.json NEW.json [--threshold PCT]

Both the raw bench-record form (the dict bench.py / bigdl_tpu.bench
emit) and the driver wrapper form ({"n", "cmd", "rc", "tail",
"parsed"}) are accepted — the wrapper's "parsed" block is compared when
present. Nested sub-records (ab variants, cpu_fallback_smoke, ...) are
walked too, so per-config latencies get their own rows.

A metric regresses when it moves in its bad direction by more than
--threshold percent (default 5): latencies and byte footprints UP,
throughput DOWN. The memory report's headline scalars
(hbm_static_total_bytes, hbm_device_peak_bytes, jit_peak_temp_bytes)
get their own --max-hbm-regress-pct threshold (default: --threshold);
the decode roofline and the critical-path dispatch overhead
(dispatch_overhead_ms) ride tighter ratchets
(--max-roofline-regress-pct / --max-dispatch-regress-pct, default 2).
Records missing any block — memory, jit_compile_table, observability,
or individual metric keys — are fine: only keys present in BOTH files
are compared. Exit status: 0 no regressions, 1 regressions found,
2 usage / unreadable input.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Optional, Tuple

# comparable scalar fields -> direction ("lower" / "higher" is better)
METRIC_DIRECTIONS = {
    "first_token_ms": "lower",
    "first_token_ms_raw": "lower",
    "next_token_ms": "lower",
    "rest_token_ms": "lower",
    "ttft_p50_ms": "lower",
    "tpot_p50_ms": "lower",
    "decode_ideal_ms": "lower",
    "kv_cache_bytes": "lower",
    "weight_bytes": "lower",
    "serving_tokens_per_s": "higher",
    "tokens_per_s": "higher",
    # overload lanes (bench_serving "overload" block): at <=1x offered
    # load shed_total/brownout_level_max must stay zero (any growth is
    # inf% and flags), at 3x goodput dropping is the regression
    "goodput_tokens_per_s": "higher",
    "shed_total": "lower",
    "brownout_level_max": "lower",
    # shared-prefix lane (bench_serving "prefix_share" block): the
    # fraction of looked-up prompt tokens served from radix-shared
    # pages must not erode, and allocation stalls against the page
    # pool must not grow at the same offered load
    "prefix_hit_tokens_frac": "higher",
    "page_pool_exhausted": "lower",
    # SLO lane (bench_serving overload block, <=1x lanes): burn rate
    # and active alerts must stay zero below capacity (slo_alerts is
    # additionally zero-gated), and the fraction of TTFT/TPOT
    # observations inside their QoS targets must not erode. The 3x
    # lane's slo_burn_rate_overload is deliberately NOT here — alerts
    # firing under deliberate overload is the feature working
    "slo_burn_rate_max": "lower",
    "slo_alerts": "lower",
    "slo_compliance_ttft": "higher",
    "slo_compliance_tpot": "higher",
    # golden-canary byte mismatches (router lane): also zero-gated — a
    # single mismatch between byte-identical seeded replicas means a
    # replica decoded garbage
    "canary_failures": "lower",
    # rolling-restart lane (bench_serving router_bench.restart block):
    # a planned restart must lose no requests (http_5xx), re-decode no
    # tokens the fleet already generated (recomputed_tokens_total —
    # live migration ships them instead), and land every attempted
    # sequence handoff (migrations_failed). All three sit at zero on a
    # healthy baseline, so any growth flags as inf%.
    "http_5xx": "lower",
    "recomputed_tokens_total": "lower",
    "migrations_failed": "lower",
    "decode_mfu": "higher",
    "prefill_mfu": "higher",
    "decode_hbm_roofline_util": "higher",
}

# memory-report headline scalars (bench "memory" block): compared
# under --max-hbm-regress-pct instead of --threshold
HBM_METRICS = {
    "hbm_static_total_bytes": "lower",
    "hbm_device_peak_bytes": "lower",
    "jit_peak_temp_bytes": "lower",
}

# robustness counters pulled out of the "observability" registry
# summary (its other series churn per run and stay skipped). Summary
# keys carry label suffixes (`...{reason="nan_logits"}`); matching is
# by family-name prefix. A run that starts quarantining requests or
# retrying steps where the baseline did not IS a regression even when
# every latency improved.
ROBUSTNESS_COUNTERS = (
    "bigdl_tpu_requests_quarantined_total",
    "bigdl_tpu_step_retries_total",
    "bigdl_tpu_requests_cancelled_total",
    "bigdl_tpu_requests_shed_total",
    "bigdl_tpu_router_failovers_total",
    "bigdl_tpu_router_replays_total",
    "bigdl_tpu_router_breaker_trips_total",
    # KV-handoff wire health: retries and local-decode fallbacks both
    # mean a decode target failed to take a transfer
    "bigdl_tpu_handoff_retries_total",
    "bigdl_tpu_handoff_fallbacks_total",
    # autoscaler guard activity: a refused or skipped decision means a
    # scale action ran into a hard guard (last-healthy, bounds, admin
    # lock) — more of those at the same load is a control regression.
    # Applied decisions ("up"/"down"/flips) are intentionally NOT
    # gated: the autoscale lane forces them by design. Label order is
    # declaration order (action first), so a family{action=" prefix
    # selects exactly these.
    'bigdl_tpu_autoscaler_decisions_total{action="refused',
    'bigdl_tpu_autoscaler_decisions_total{action="skipped',
    # perf-regression sentinel trips (observability/sentinel.py) —
    # additionally zero-gated below: a gated lane must never ship a
    # run whose own sentinel fired
    "bigdl_tpu_perf_regression_total",
    # quality-regression sentinel trips (observability/quality.py) —
    # also zero-gated: the run itself watched its decode quality drift
    "bigdl_tpu_quality_regression_total",
    # golden-canary byte mismatches (serving/canary.py) — also
    # zero-gated: byte-identical seeded replicas must agree
    "bigdl_tpu_router_canary_failures_total",
    # live-migration health: a failed sequence migration means a
    # planned drain fell back to journal replay (recompute), and a
    # rejected wire frame means a corrupt/skewed internal payload
    # reached a replica
    'bigdl_tpu_migrations_total{outcome="failed',
    "bigdl_tpu_handoff_rejects_total",
)

# counters that must be exactly 0 in the candidate run, baseline or
# not: a sentinel trip means the run itself detected a decode (or
# decode-quality) regression while it was happening; an SLO alert or
# a canary byte mismatch in a gated lane means the run violated its
# own objectives
ZERO_COUNTERS = ("bigdl_tpu_perf_regression_total",
                 "bigdl_tpu_quality_regression_total",
                 "slo_alerts", "canary_failures")

# the router's flat counters block (bench_serving --replicas embeds
# GET /v1/router/stats as `router_bench.router`): every one of these
# counts a recovery action, so MORE of them between two runs of the
# same load is a robustness regression even when throughput improved
ROUTER_COUNTERS = {
    "failovers": "lower",
    "replays": "lower",
    "breaker_trips": "lower",
    "quarantined": "lower",
    "rerouted_503": "lower",
    "shed_429": "lower",
    "stream_errors": "lower",
    # disaggregated-serving health: handoff retries/fallbacks count
    # failed KV transfers to decode replicas; autoscale_refused counts
    # scale decisions stopped by a hard guard. Spawn/retire/flip
    # counters are not gated — the autoscale lane drives them on
    # purpose.
    "handoff_retries": "lower",
    "handoff_fallbacks": "lower",
    "autoscale_refused": "lower",
    # golden-canary byte mismatches: zero-gated via ZERO_COUNTERS too
    "canary_failures": "lower",
    # live-migration recovery actions (flat router counters): failed
    # handoffs, continuation fallbacks to journal replay, recomputed
    # tokens, torn journal records — all zero on a clean fleet
    "migration_failed": "lower",
    "sequences_migrate_failed": "lower",
    "migration_fallback_replays": "lower",
    "recomputed_tokens_total": "lower",
    "journal_torn_records": "lower",
}

# host dispatch overhead of the decode step (bench_serving
# "critical_path" block, EWMA of dispatch-return time per step): the
# host-overhead number the paper optimizes, so it gets its own
# (tighter) --max-dispatch-regress-pct ratchet, lower-is-better
DISPATCH_METRICS = {
    "dispatch_overhead_ms": "lower",
}

# the HBM-bandwidth roofline utilization of the decode step is the
# tentpole serving efficiency number: it gets a RATCHET — its own
# (tighter) --max-roofline-regress-pct threshold, higher-is-better,
# instead of riding the generic --threshold. decode_mfu rides the same
# ratchet: the fused decode path moves both together (one dispatch,
# same bytes), so a run that holds roofline but drops MFU is hiding a
# compute regression behind the bandwidth number
ROOFLINE_METRICS = {
    "decode_hbm_roofline_util": "higher",
    "decode_mfu": "higher",
}

# the per-format golden NLL budget (quality block, nats/token —
# observability/quality.golden_nll_allowance from the refreshed
# ACCURACY.md deltas): a SHRINK-ONLY ratchet with its own (tight)
# --max-nll-regress-pct, lower-is-better — quantization quality may
# improve freely but a budget that grows means the format got worse
# (or someone quietly loosened the table)
NLL_METRICS = {
    "nll_delta_vs_bf16": "lower",
}


def load_record(path: str) -> dict:
    """Read a BENCH json; unwrap the driver's {"parsed": ...} wrapper
    when that is what we got."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object, got "
                         f"{type(doc).__name__}")
    if set(doc) >= {"cmd", "rc", "parsed"}:
        parsed = doc.get("parsed")
        if not isinstance(parsed, dict):
            raise ValueError(
                f"{path}: wrapper has no parsed bench record "
                f"(parsed={parsed!r}) — nothing to compare")
        return parsed
    return doc


def flatten_metrics(rec: dict, prefix: str = "",
                    out: Optional[Dict[str, Tuple[float, str]]] = None,
                    depth: int = 0) -> Dict[str, Tuple[float, str]]:
    """{dotted.name: (value, direction)} for every comparable scalar,
    recursing into sub-record dicts (ab variants etc.). Tolerant by
    construction: absent keys/blocks simply contribute nothing (a
    pre-memory or pre-compile-table record still compares on whatever
    it has)."""
    if out is None:
        out = {}
    if not isinstance(rec, dict):
        return out
    for key, val in rec.items():
        name = f"{prefix}{key}"
        if key in METRIC_DIRECTIONS and isinstance(val, (int, float)) \
                and not isinstance(val, bool):
            out[name] = (float(val), METRIC_DIRECTIONS[key])
        elif key in HBM_METRICS and isinstance(val, (int, float)) \
                and not isinstance(val, bool):
            out[name] = (float(val), HBM_METRICS[key])
        elif key in DISPATCH_METRICS and isinstance(val, (int, float)) \
                and not isinstance(val, bool):
            out[name] = (float(val), DISPATCH_METRICS[key])
        elif key in NLL_METRICS and isinstance(val, (int, float)) \
                and not isinstance(val, bool):
            out[name] = (float(val), NLL_METRICS[key])
        elif key == "value" and isinstance(val, (int, float)) \
                and not isinstance(val, bool) and rec.get("unit") == "ms":
            # the headline {"metric": ..., "value": ..., "unit": "ms"}
            # row: a latency, keyed by its metric name
            label = rec.get("metric", "value")
            out[f"{prefix}{label}"] = (float(val), "lower")
        elif key == "observability" and isinstance(val, dict):
            # only the robustness counters: the full summary (latency
            # histograms, per-phase gauges) churns per environment
            for mk, mv in val.items():
                if mk.startswith(ROBUSTNESS_COUNTERS) \
                        and isinstance(mv, (int, float)) \
                        and not isinstance(mv, bool):
                    out[f"{name}.{mk}"] = (float(mv), "lower")
        elif key == "router" and isinstance(val, dict) \
                and isinstance(val.get("counters"), dict):
            # embedded GET /v1/router/stats: gate the recovery-action
            # counters lower-is-better (replica rows and config churn
            # per run and stay skipped)
            for mk, direction in ROUTER_COUNTERS.items():
                mv = val["counters"].get(mk)
                if isinstance(mv, (int, float)) \
                        and not isinstance(mv, bool):
                    out[f"{name}.counters.{mk}"] = (float(mv), direction)
        elif key == "memory" and isinstance(val, dict):
            # only the headline scalars: the snapshot's nested static/
            # device/headroom dicts churn per environment
            for mk, direction in HBM_METRICS.items():
                mv = val.get(mk)
                if isinstance(mv, (int, float)) \
                        and not isinstance(mv, bool):
                    out[f"{name}.{mk}"] = (float(mv), direction)
        elif isinstance(val, dict) and depth < 3 \
                and key not in ("observability", "jit_compile_table",
                                "prepack"):
            # "prepack" is the load-time weight-prepack report (mode,
            # counts, one-time transform ms) — informational, never a
            # per-token metric, so it stays out of the comparison
            flatten_metrics(val, f"{name}.", out, depth + 1)
    return out


def diff(old: Dict[str, Tuple[float, str]],
         new: Dict[str, Tuple[float, str]],
         threshold_pct: float,
         hbm_threshold_pct: Optional[float] = None,
         roofline_threshold_pct: Optional[float] = None,
         dispatch_threshold_pct: Optional[float] = None,
         nll_threshold_pct: Optional[float] = None):
    """Returns (rows, regressions): rows are (name, old, new, pct,
    direction, regressed) for every metric present in both files.
    Memory-report scalars (HBM_METRICS keys) regress past
    ``hbm_threshold_pct`` (default: ``threshold_pct``); the decode
    roofline ratchet (ROOFLINE_METRICS) past ``roofline_threshold_pct``
    (default 2); the host dispatch-overhead ratchet (DISPATCH_METRICS)
    past ``dispatch_threshold_pct`` (default 2); the golden NLL budget
    (NLL_METRICS) past ``nll_threshold_pct`` (default 2,
    shrink-only)."""
    if hbm_threshold_pct is None:
        hbm_threshold_pct = threshold_pct
    if roofline_threshold_pct is None:
        roofline_threshold_pct = 2.0
    if dispatch_threshold_pct is None:
        dispatch_threshold_pct = 2.0
    if nll_threshold_pct is None:
        nll_threshold_pct = 2.0
    rows = []
    regressions = []
    for name in sorted(set(old) & set(new)):
        o, direction = old[name]
        n, _ = new[name]
        if o == 0:
            pct = 0.0 if n == 0 else float("inf") * (1 if n > 0 else -1)
        else:
            pct = (n - o) / abs(o) * 100.0
        leaf = name.rsplit(".", 1)[-1]
        if leaf in HBM_METRICS:
            limit = hbm_threshold_pct
        elif leaf in ROOFLINE_METRICS:
            limit = roofline_threshold_pct
        elif leaf in DISPATCH_METRICS:
            limit = dispatch_threshold_pct
        elif leaf in NLL_METRICS:
            limit = nll_threshold_pct
        else:
            limit = threshold_pct
        bad = pct > limit if direction == "lower" else pct < -limit
        if n > 0 and any(z in name for z in ZERO_COUNTERS):
            bad = True      # zero-gated: nonzero is a failure outright
        rows.append((name, o, n, pct, direction, bad))
        if bad:
            regressions.append(name)
    # zero-gated counters present only in the candidate still fail:
    # the baseline predates the sentinel, the trip is real either way
    for name in sorted(set(new) - set(old)):
        n, direction = new[name]
        if n > 0 and any(z in name for z in ZERO_COUNTERS):
            rows.append((name, 0.0, n, float("inf"), direction, True))
            regressions.append(name)
    return rows, regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old", help="baseline BENCH json")
    ap.add_argument("new", help="candidate BENCH json")
    ap.add_argument("--threshold", type=float, default=5.0,
                    help="regression threshold in percent (default 5)")
    ap.add_argument("--max-hbm-regress-pct", type=float, default=None,
                    help="separate threshold for the memory report's "
                         "HBM scalars (default: --threshold)")
    ap.add_argument("--max-roofline-regress-pct", type=float,
                    default=2.0,
                    help="ratchet threshold for "
                         "decode_hbm_roofline_util and decode_mfu "
                         "(default 2; higher-is-better)")
    ap.add_argument("--max-dispatch-regress-pct", type=float,
                    default=2.0,
                    help="ratchet threshold for dispatch_overhead_ms "
                         "(default 2; lower-is-better)")
    ap.add_argument("--max-nll-regress-pct", type=float, default=2.0,
                    help="shrink-only ratchet threshold for the "
                         "quality block's nll_delta_vs_bf16 golden "
                         "budget (default 2; lower-is-better)")
    args = ap.parse_args(argv)

    try:
        old = flatten_metrics(load_record(args.old))
        new = flatten_metrics(load_record(args.new))
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2

    rows, regressions = diff(old, new, args.threshold,
                             args.max_hbm_regress_pct,
                             args.max_roofline_regress_pct,
                             args.max_dispatch_regress_pct,
                             args.max_nll_regress_pct)
    if not rows:
        print("bench_diff: no comparable metrics between "
              f"{args.old} and {args.new}", file=sys.stderr)
        return 0

    width = max(len(r[0]) for r in rows)
    print(f"{'metric':<{width}}  {'old':>14}  {'new':>14}  {'delta':>9}")
    for name, o, n, pct, direction, bad in rows:
        arrow = "" if not bad else \
            "  REGRESSION" + (" (want lower)" if direction == "lower"
                              else " (want higher)")
        print(f"{name:<{width}}  {o:>14.4f}  {n:>14.4f}  {pct:>+8.2f}%"
              f"{arrow}")
    missing = sorted(set(old) ^ set(new))
    if missing:
        print(f"(not in both files, skipped: {', '.join(missing)})")
    if regressions:
        print(f"{len(regressions)} regression(s) past "
              f"{args.threshold:g}%: {', '.join(regressions)}")
        return 1
    print(f"no regressions past {args.threshold:g}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
