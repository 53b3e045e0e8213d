"""The seven per-layer metrics of layer "start-up" (they move
``setup_s``): each reader on a canned scrape, their entries in
``BENCHMARK.json``, and one tiny CPU run that reports the one count
among them."""

import json

import pytest

import _paths
from harness import layer_metrics, promtext, spec
from test_bench_run_tiny import LINE_KEYS, _run

BENCH = spec.load_benchmark(_paths.ROOT)
SERVING = ["mistral7b-chat-steady", "chatglm2-6b-docqa-shared",
           "mistral7b-batch-closed", "deepseekv2-ep8-reason-closed",
           "mistral7b-chat-bursty", "dots3-ep8-longdoc-closed",
           "evabyte-longbytes-closed", "deepseekv32-ep8-reason-closed",
           "mimo-v25-ep8-mixedlen-closed",
           "trinity-mini-ep4-thinking-closed",
           "sdar-30b-ep4-fixedlen-closed"]
# metric -> (unit, better, source, what the canned scrape must read)
METRICS = {
    "setup_pre_engine_s": ("s", "lower", "program_span", 21.5),
    "setup_trace_s": ("s", "lower", "program_span", 4.0 + 1.5 + 0.25),
    "setup_lower_s": ("s", "lower", "program_span", 2.0 + 0.5),
    "setup_compile_s": ("s", "lower", "program_span", 0.75),
    "setup_cache_load_s": ("s", "lower", "program_span", 3.0 + 1.0),
    "setup_cache_hit_share": ("%", "higher", "program_counter", 75.0),
    "setup_first_token_s": ("s", "lower", "program_span", 40.25),
}

STAGE = "bigdl_tpu_jit_stage_seconds_total"
CACHE = "bigdl_tpu_compile_cache_requests_total"
MARK = "bigdl_tpu_startup_mark_seconds"
SCRAPE = f"""
# TYPE {STAGE} counter
{STAGE}{{fn="engine_prefill",stage="trace"}} 4
{STAGE}{{fn="engine_prefill",stage="lower"}} 2
{STAGE}{{fn="engine_prefill",stage="compile"}} 0
{STAGE}{{fn="engine_prefill",stage="cache_load"}} 3
{STAGE}{{fn="engine_prefill",stage="memory_analysis"}} 0
{STAGE}{{fn="engine_prefill",stage="first_run"}} 0.125
{STAGE}{{fn="engine_decode_resident",stage="trace"}} 1.5
{STAGE}{{fn="engine_decode_resident",stage="lower"}} 0.5
{STAGE}{{fn="engine_decode_resident",stage="compile"}} 0
{STAGE}{{fn="engine_decode_resident",stage="cache_load"}} 1
{STAGE}{{fn="engine_decode_resident",stage="first_run"}} 0.0625
{STAGE}{{fn="untracked",stage="trace"}} 0.25
{STAGE}{{fn="untracked",stage="compile"}} 0.75
# TYPE {CACHE} counter
{CACHE}{{fn="engine_prefill",outcome="hit"}} 2
{CACHE}{{fn="engine_prefill",outcome="miss"}} 0
{CACHE}{{fn="engine_decode_resident",outcome="hit"}} 1
{CACHE}{{fn="engine_decode_resident",outcome="miss"}} 1
{CACHE}{{fn="untracked",outcome="hit"}} 5
{CACHE}{{fn="untracked",outcome="miss"}} 90
# TYPE {MARK} gauge
{MARK}{{mark="engine_init_begin"}} 21.5
{MARK}{{mark="engine_init_end"}} 22
{MARK}{{mark="listening"}} 22.0625
{MARK}{{mark="first_request"}} 23
{MARK}{{mark="first_token"}} 40.25
{MARK}{{mark="last_compile_end"}} 61
"""


def _reader(metric):
    return _paths.BENCH / "layer_metrics" / f"{metric}.py"


def _obs(start, end=None):
    return {"counters_start": start, "counters_end": end, "trace": None,
            "client": None, "work": None, "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_takes_the_absolute_value_at_the_windows_start(metric):
    start = promtext.parse(SCRAPE)
    # whatever the window added is not set-up's: the end is not read
    end = {k: v * 3 for k, v in start.items()}
    assert layer_metrics.read_metric(_reader(metric), _obs(start, end)) \
        == pytest.approx(METRICS[metric][3])


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_finds_nothing_where_the_series_is_missing(metric):
    """The parent of the PR that brought the account, and a runner that
    hands its readers no scrape (the training cell)."""
    other = promtext.parse(
        "bigdl_tpu_jit_compiles_total{fn=\"engine_prefill\"} 2\n")
    assert layer_metrics.read_metric(_reader(metric), _obs(other)) is None
    assert layer_metrics.read_metric(_reader(metric), _obs(None)) is None
    assert layer_metrics.read_metric(_reader(metric), {}) is None


def test_hit_share_leaves_the_untracked_programs_out():
    start = promtext.parse(SCRAPE)
    path = _reader("setup_cache_hit_share")
    assert layer_metrics.read_metric(path, _obs(start)) == 75.0
    # with them it would read 8 of 99
    only = {k: v for k, v in start.items()
            if dict(k[1]).get("fn") == "untracked"}
    assert layer_metrics.read_metric(path, _obs(only)) is None


def test_a_mark_not_reached_reads_nothing():
    start = {k: v for k, v in promtext.parse(SCRAPE).items()
             if dict(k[1]).get("mark") != "first_token"}
    assert layer_metrics.read_metric(
        _reader("setup_first_token_s"), _obs(start)) is None
    assert layer_metrics.read_metric(
        _reader("setup_pre_engine_s"), _obs(start)) == 21.5


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_benchmark_json_lists_it_for_the_eleven_serving_cells(metric):
    entries = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert len(entries) == 1
    unit, better, source, _ = METRICS[metric]
    assert entries[0] == {
        "name": metric, "unit": unit, "better": better, "source": source,
        "layer": "start-up", "moves": "setup_s", "workloads": SERVING}


def test_they_are_the_only_metrics_that_move_setup_s():
    moving = {m["name"] for m in BENCH["per_layer"]
              if m["moves"] == "setup_s"}
    assert moving == set(METRICS)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert cells - set(SERVING) == {"mistral7b-qlora-alpaca"}
    for cell in sorted(cells):
        names = {m["name"] for m in
                 spec.Cell(cell, _paths.ROOT).per_layer()}
        assert (names >= set(METRICS)) == (cell in SERVING)
        assert (cell in SERVING) or not (names & set(METRICS))


def test_the_program_names_the_series_as_the_readers_do():
    from harness import startup_account

    from bigdl_tpu.observability import compile_watch as cw

    assert (startup_account.STAGE_SECONDS, startup_account.CACHE_REQUESTS,
            startup_account.MARK_SECONDS) == (
                cw.STAGE_SECONDS, cw.CACHE_REQUESTS, cw.MARK_SECONDS)
    assert startup_account.UNTRACKED == {"fn": cw.UNTRACKED}
    assert {"trace", "lower", "compile", "cache_load"} < set(cw.STAGES)
    assert {"engine_init_begin", "first_token"} < set(cw.MARKS)


def test_tiny_run_reports_the_cache_hit_share_and_no_time():
    r = _run(_paths.ROOT, "--workload", "mistral7b-chat-steady",
             "--seed", str(2 ** 31 + 55), "--seconds", "2", "--trace", "1",
             "--tiny")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True, r.stdout[-3000:]
    share = line["metrics"]["setup_cache_hit_share"]
    assert share["unit"] == "%" and 0.0 <= share["value"] <= 100.0
    # a CPU run yields no time: the six readings in seconds stay out
    assert not (set(METRICS) - {"setup_cache_hit_share"}) \
        & set(line["metrics"])
