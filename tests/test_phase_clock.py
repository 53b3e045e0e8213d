"""The engine step's phase clock (observability/tracing.PhaseClock):
every part of ``LLMEngine.step`` is a span on the profiler's clock and
a share of one ``bigdl_tpu_step_phase_seconds`` sample per step; the
admission boundary counts its prefill chunks and tokens; the API
server measures a token's way from the engine to the wire and a
request's way from its request line to the engine's queue; every
Pallas kernel has a name."""

import glob
import json
import time
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.observability import MetricsRegistry, RequestTracer
from bigdl_tpu.observability.disttrace import new_span_id, new_trace_id
from bigdl_tpu.observability.metrics import STEP_WALL_BUCKETS_S
from bigdl_tpu.observability.tracing import (DECODE_STEP_PHASES,
                                             ADMISSION_KIND, STEP_KINDS,
                                             WORKING_STEP_PHASES,
                                             PhaseClock)
from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from bigdl_tpu.utils.testing import tiny_random_model

KINDS = {"slab": {}, "paged": {"kv_page_size": 16, "prefix_sharing": "on"}}
CHUNK = 8
PROMPT_LENS = (13, 5, 20)


def _engine(kind: str, **kw) -> LLMEngine:
    cfg = dict(max_batch=4, max_seq=64, prefill_bucket=CHUNK,
               prefill_chunk=CHUNK, prefix_cache_entries=0)
    cfg.update(KINDS[kind])
    cfg.update(kw)
    return LLMEngine(tiny_random_model(seed=0), EngineConfig(**cfg),
                     registry=MetricsRegistry(),
                     tracer=RequestTracer(event_log_path=""))


def _labelled(summ, series: str, **labels) -> list:
    """The summary's entries of ``series`` whose labels include
    ``labels`` (what the benchmark's readers sum)."""
    want = ['%s="%s"' % kv for kv in labels.items()]
    return [v for k, v in summ.items() if k.startswith(series + "{")
            and all(w in k for w in want)]


def _phase(eng, name: str, field: str = "count", **labels) -> float:
    """One label of the step histogram, over its kinds: what the
    parent's single label held."""
    return sum(v[field] for v in _labelled(
        eng.registry.summary(), "bigdl_tpu_step_phase_seconds",
        phase=name, **labels))


def _counter(eng, series: str) -> float:
    return eng.registry.summary().get(series, 0)


@pytest.fixture(scope="module", params=sorted(KINDS))
def driven(request):
    """One engine of each kind driven through three requests and some
    idle steps, with each step's wall time taken from outside."""
    eng = _engine(request.param)
    rng = np.random.default_rng(5)
    for i, n in enumerate(PROMPT_LENS):
        eng.add_request(f"r{i}", rng.integers(1, 250, n).tolist(),
                        SamplingParams(max_tokens=6))
    decoded_wall = 0.0
    n_steps = 0
    while eng.has_unfinished():
        before = _phase(eng, "device")
        t0 = time.perf_counter()
        eng.step()
        wall = time.perf_counter() - t0
        if _phase(eng, "device") > before:
            decoded_wall += wall
        n_steps += 1
        assert n_steps < 500
    for _ in range(3):
        assert eng.step() is False      # idle steps observe nothing
    return {"kind": request.param, "eng": eng,
            "decoded_wall": decoded_wall}


@pytest.mark.parametrize("phase", WORKING_STEP_PHASES)
def test_one_sample_per_working_step(driven, phase):
    eng = driven["eng"]
    steps = _counter(eng, "bigdl_tpu_engine_steps_total")
    assert steps > 0
    assert _phase(eng, phase) == steps


@pytest.mark.parametrize("phase", DECODE_STEP_PHASES)
def test_one_sample_per_step_that_decoded(driven, phase):
    eng = driven["eng"]
    decoded = _phase(eng, "device")
    assert 0 < decoded < _counter(eng, "bigdl_tpu_engine_steps_total")
    assert _phase(eng, phase) == decoded


def test_host_plus_device_is_the_wall_of_the_steps_that_decoded(driven):
    eng = driven["eng"]
    inside = _phase(eng, "host", "sum") + _phase(eng, "device", "sum")
    # the clock starts a few statements into step() and stops before
    # the samples are observed: the outside wall is a little longer
    assert inside <= driven["decoded_wall"]
    assert inside == pytest.approx(driven["decoded_wall"], rel=0.05,
                                   abs=0.005)
    parts = sum(_phase(eng, p, "sum") for p in
                ("dispatch", "sample", "emit"))
    assert parts <= _phase(eng, "host", "sum")


def test_every_step_has_one_kind_and_a_chunk_makes_it_chunk(driven):
    """At most one chunk a step: the working steps under ``chunk`` are
    the chunks dispatched, and the walls in bigdl_tpu_tpot_seconds are
    the steps that decoded, kind for kind."""
    eng = driven["eng"]
    chunks = _counter(eng, "bigdl_tpu_prefill_chunks_total")
    steps = _counter(eng, "bigdl_tpu_engine_steps_total")
    summ = eng.registry.summary()
    for phase in WORKING_STEP_PHASES:
        assert _phase(eng, phase, kind="chunk") == chunks
        assert _phase(eng, phase, kind="plain") == steps - chunks
    for kind in STEP_KINDS:
        wall = summ['bigdl_tpu_tpot_seconds{kind="%s"}' % kind]
        assert wall["count"] == _phase(eng, "device", kind=kind) > 0
        assert wall["sum"] == pytest.approx(
            _phase(eng, "host", "sum", kind=kind)
            + _phase(eng, "device", "sum", kind=kind))
    # the first request's chunks ran with no slot decoding yet
    assert _phase(eng, "device", kind="chunk") < chunks


def test_request_phases_count_as_before(driven):
    eng = driven["eng"]
    n = len(PROMPT_LENS)
    assert _phase(eng, "queue_wait", kind=ADMISSION_KIND) == n
    assert _phase(eng, "prefill", kind=ADMISSION_KIND) == n
    assert _phase(eng, "queue_wait") == _phase(eng, "prefill") == n
    assert _counter(eng, "bigdl_tpu_admissions_total") == n
    # every request decodes 5 tokens after its admission's first
    assert _counter(eng, "bigdl_tpu_tokens_generated_total") == 6 * n


def test_cache_phase_has_time_only_where_pages_are_managed(driven):
    cache_s = _phase(driven["eng"], "cache", "sum")
    if driven["kind"] == "paged":
        assert cache_s > 0.0
    else:
        assert cache_s == 0.0


def test_prefill_chunks_and_tokens_count_the_dispatches(driven):
    eng = driven["eng"]
    # a prompt's bucket is the next power-of-two multiple of the chunk,
    # and admission runs ceil(len / chunk) chunks of the chunk's width
    chunks = sum(-(-n // CHUNK) for n in PROMPT_LENS)
    assert _counter(eng, "bigdl_tpu_prefill_chunks_total") == chunks
    assert _counter(
        eng, 'bigdl_tpu_prefill_tokens_total{kind="prompt"}') \
        == sum(PROMPT_LENS)
    assert _counter(
        eng, 'bigdl_tpu_prefill_tokens_total{kind="padding"}') \
        == chunks * CHUNK - sum(PROMPT_LENS)


def test_a_shared_prefix_removes_its_chunks_from_the_count():
    eng = _engine("paged")
    doc = list(range(1, 33))                    # two pages, four chunks
    for i, tail in enumerate(([40, 41, 42], [50, 51])):
        eng.add_request(f"q{i}", doc + tail, SamplingParams(max_tokens=2))
        while eng.has_unfinished():
            eng.step()
    # first: ceil(35 / 8) = 5 chunks; second: 32 tokens hit, one chunk
    assert _counter(eng, "bigdl_tpu_prefill_chunks_total") == 6
    assert _counter(
        eng, 'bigdl_tpu_prefill_tokens_total{kind="prompt"}') == 35 + 2


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_dispatch_and_device_keep_their_intervals(kind):
    """The histogram's dispatch and device are the intervals the
    decode_step spans carry (dispatch_ms, device_ms)."""
    eng = _engine(kind)
    tid = new_trace_id()
    eng.add_request("t", [1, 2, 3, 4], SamplingParams(max_tokens=6),
                    trace=(tid, new_span_id()))
    while eng.has_unfinished():
        eng.step()
    steps = [s for s in eng.spans.spans_for(tid)
             if s["name"] == "decode_step"]
    assert len(steps) == _phase(eng, "device") == 5
    for phase, attr in (("dispatch", "dispatch_ms"),
                        ("device", "device_ms")):
        from_spans = sum(s["attrs"][attr] for s in steps)
        assert _phase(eng, phase, "sum") * 1000.0 == pytest.approx(
            from_spans, abs=0.001 * len(steps))
    assert eng.stats_snapshot()["dispatch_overhead_ms"] > 0.0


def test_a_failed_step_observes_nothing_and_leaves_no_total():
    eng = _engine("slab")
    eng.add_request("x", [1, 2, 3], SamplingParams(max_tokens=2))
    boom = RuntimeError("boom")

    def raising():
        raise boom

    inner = eng._admission_step
    eng._admission_step = raising
    eng.step()                              # retried or quarantined
    eng._admission_step = inner
    assert _phase(eng, "admission") == 0
    while eng.has_unfinished():
        eng.step()
    assert _phase(eng, "sweep") == _counter(
        eng, "bigdl_tpu_engine_steps_total")


# -- the clock itself ---------------------------------------------------------


class _Spans:
    def __init__(self):
        self.log = []

    def __call__(self, name):
        spans = self

        class Span:
            def __enter__(self):
                spans.log.append(("open", name))

            def __exit__(self, *exc):
                spans.log.append(("close", name))

        return Span()


def _hand_clock(spans=None):
    """A clock over a registry of its own: ``(registry, clock)``."""
    reg = MetricsRegistry()
    clock = PhaseClock(
        reg.histogram("t_phase_seconds", "x",
                      labelnames=("phase", "kind")),
        reg.histogram("t_wall_seconds", "x", labelnames=("kind",),
                      buckets=STEP_WALL_BUCKETS_S),
        spans or _Spans())
    return reg, clock


def _hand_phase(reg, name: str, kind: str) -> dict:
    return reg.summary().get(
        't_phase_seconds{phase="%s",kind="%s"}' % (name, kind),
        {"count": 0, "sum": 0.0})


def test_clock_names_spans_and_sums_children_into_derived_labels():
    spans = _Spans()
    reg, clock = _hand_clock(spans)
    clock.begin()
    with clock.phase("admission"):
        with clock.phase("cache.radix_match", child=True):
            time.sleep(0.002)
        with clock.phase("admission.wait", child=True):
            pass
    with clock.phase("dispatch"):
        with clock.phase("cache.cow", child=True):
            time.sleep(0.002)
    with clock.phase("device"):
        time.sleep(0.002)
    assert [n for kind, n in spans.log if kind == "open"] == [
        "engine.admission", "cache.radix_match", "admission.wait",
        "engine.dispatch", "cache.cow", "engine.device"]
    assert 0.004 <= clock.seconds("cache") <= clock.seconds(
        "admission") + clock.seconds("dispatch")
    assert clock.seconds("admission.wait") == 0.0     # trace-only
    clock.end(worked=True)
    for name in WORKING_STEP_PHASES + DECODE_STEP_PHASES:
        assert _hand_phase(reg, name, "plain")["count"] == 1
    host = _hand_phase(reg, "host", "plain")["sum"]
    assert host >= clock.seconds("admission") + clock.seconds("dispatch")
    # the next step starts from nothing; an idle one observes nothing
    clock.begin()
    assert clock.seconds("cache") == 0.0
    clock.end(worked=False)
    assert _hand_phase(reg, "sweep", "plain")["count"] == 1


class _Ticks:
    """The clock's clock, by hand: time passes only where a test says."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds


@pytest.fixture
def ticks(monkeypatch):
    from bigdl_tpu.observability import tracing

    fake = _Ticks()
    monkeypatch.setattr(tracing, "time", fake)
    return fake


def _hand_step(clock, ticks, chunk: bool, decode: bool = True) -> None:
    """One step driven by hand, the way ``LLMEngine.step`` drives it:
    6 ms of admission with a chunk (1 ms of puts, 2 ms of blocked fetch),
    3 ms without; 1 ms of puts, 2 ms of device and 1 ms of fetch in a
    decode."""
    clock.begin()
    with clock.phase("sweep"):
        pass
    with clock.phase("admission"):
        ticks.sleep(0.003)              # ahead of the decode's start
        if chunk:
            with clock.phase("admission.h2d", child=True):
                ticks.sleep(0.001)
            clock.mark_chunk()
            with clock.phase("admission.wait", child=True):
                ticks.sleep(0.002)
    if decode:
        with clock.phase("dispatch"):
            with clock.phase("dispatch.h2d", child=True):
                ticks.sleep(0.001)
        with clock.phase("device"):
            ticks.sleep(0.002)
        with clock.phase("sample"):
            with clock.phase("sample.fetch", child=True):
                ticks.sleep(0.001)
        with clock.phase("emit"):
            pass
    with clock.phase("observe"):
        pass
    clock.end(worked=True)


def test_a_step_marked_chunk_lands_under_chunk_and_the_next_under_plain(
        ticks):
    reg, clock = _hand_clock()
    _hand_step(clock, ticks, chunk=True)
    for name in WORKING_STEP_PHASES + DECODE_STEP_PHASES:
        assert _hand_phase(reg, name, "chunk")["count"] == 1, name
        assert _hand_phase(reg, name, "plain")["count"] == 0, name
    _hand_step(clock, ticks, chunk=False)       # begin() forgot the mark
    _hand_step(clock, ticks, chunk=True, decode=False)
    for name in DECODE_STEP_PHASES:
        assert _hand_phase(reg, name, "chunk")["count"] == 1, name
        assert _hand_phase(reg, name, "plain")["count"] == 1, name
    for name in WORKING_STEP_PHASES:
        assert _hand_phase(reg, name, "chunk")["count"] == 2, name
        assert _hand_phase(reg, name, "plain")["count"] == 1, name
    summ = reg.summary()
    assert summ['t_wall_seconds{kind="chunk"}']["count"] == 1
    assert summ['t_wall_seconds{kind="plain"}']["count"] == 1


def test_h2d_and_fetch_are_the_sums_of_their_child_spans(ticks):
    reg, clock = _hand_clock()
    _hand_step(clock, ticks, chunk=True)
    _hand_step(clock, ticks, chunk=False)
    # admission.h2d + dispatch.h2d; admission.wait + sample.fetch
    assert _hand_phase(reg, "h2d", "chunk")["sum"] == pytest.approx(0.002)
    assert _hand_phase(reg, "fetch", "chunk")["sum"] == pytest.approx(0.003)
    assert _hand_phase(reg, "h2d", "plain")["sum"] == pytest.approx(0.001)
    assert _hand_phase(reg, "fetch", "plain")["sum"] == pytest.approx(0.001)
    # the children stay inside their parents' totals
    assert _hand_phase(reg, "admission", "chunk")["sum"] \
        == pytest.approx(0.006)
    assert _hand_phase(reg, "dispatch", "chunk")["sum"] \
        == pytest.approx(0.001)


def test_step_wall_is_from_begin_to_end_not_from_the_decodes_start(ticks):
    reg, clock = _hand_clock()
    _hand_step(clock, ticks, chunk=True)
    _hand_step(clock, ticks, chunk=False)
    _hand_step(clock, ticks, chunk=True, decode=False)     # no wall
    summ = reg.summary()
    # the decode alone (dispatch, device, sample) is 4 ms of each
    assert summ['t_wall_seconds{kind="chunk"}']["sum"] \
        == pytest.approx(0.006 + 0.004)
    assert summ['t_wall_seconds{kind="plain"}']["sum"] \
        == pytest.approx(0.003 + 0.004)
    for kind in STEP_KINDS:
        wall = summ['t_wall_seconds{kind="%s"}' % kind]
        assert wall["count"] == 1
        assert wall["sum"] == pytest.approx(
            _hand_phase(reg, "host", kind)["sum"]
            + _hand_phase(reg, "device", kind)["sum"])
        assert _hand_phase(reg, "device", kind)["sum"] \
            == pytest.approx(0.002)


def test_the_fine_buckets_keep_a_percentile_within_three_percent(
        monkeypatch):
    """Two clusters, the plain steps and the chunk-carrying ones of a
    byte-level cell: the 95th percentile interpolated inside its bucket,
    as the benchmark's reader does, against the exact one."""
    monkeypatch.syspath_prepend(
        str(Path(__file__).resolve().parents[1] / "benchmark"))
    from harness import promtext

    b = STEP_WALL_BUCKETS_S
    inner = [x for x in b if 0.004 <= x <= 1.0]
    assert b[3] <= 0.004 and b[-6] == 1.0
    assert max(y / x for x, y in zip(inner, inner[1:])) <= 1.06
    rng = np.random.default_rng(41)
    # (no share puts a tested percentile in the gap between the clusters,
    # where the exact one is itself an interpolation across the gap)
    for chunk_share in (0.03, 0.12, 0.4):
        n = 4000
        n_chunk = int(n * chunk_share)
        sample = np.concatenate([rng.uniform(0.024, 0.029, n - n_chunk),
                                 rng.uniform(0.200, 0.210, n_chunk)])
        reg = MetricsRegistry()
        h = reg.histogram("w_seconds", "x", buckets=b)
        for v in sample:
            h.observe(v)
        end = promtext.parse(reg.render())
        for q in (0.5, 0.9, 0.95, 0.99):
            got = promtext.histogram_quantile({}, end, "w_seconds", q)
            assert got == pytest.approx(
                float(np.percentile(sample, 100 * q)), rel=0.03), (
                    chunk_share, q)


def test_clock_closes_its_span_when_the_phase_raises():
    spans = _Spans()
    _, clock = _hand_clock(spans)
    clock.begin()
    with pytest.raises(KeyError):
        with clock.phase("sweep"):
            raise KeyError("x")
    assert spans.log == [("open", "engine.sweep"),
                         ("close", "engine.sweep")]
    assert clock.seconds("sweep") > 0.0


# -- the profiler's view ------------------------------------------------------


def test_a_profiler_capture_shows_the_phases_on_the_engine_thread(tmp_path):
    """Three steps of a paged engine under jax.profiler: the top-level
    spans lie on one thread and do not overlap, and every cache.*,
    observe.* and admission.* child lies inside its parent."""
    from jax.profiler import ProfileData

    eng = _engine("paged")
    eng.add_request("a", list(range(1, 20)), SamplingParams(max_tokens=30))
    for _ in range(4):
        eng.step()                      # "a" is decoding, all compiled
    eng.add_request("b", list(range(1, 12)) + [77, 78, 79],
                    SamplingParams(max_tokens=30))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            assert eng.step()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    top = ("engine.sweep", "engine.admission", "engine.dispatch",
           "engine.device", "engine.sample", "engine.emit",
           "engine.observe")
    threads = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events
                   if e.name.startswith(("engine.", "cache.", "observe.",
                                         "admission.", "dispatch.",
                                         "sample."))]
            if evs:
                threads.append(evs)
    assert len(threads) == 1, "the engine's spans lie on one thread"
    evs = threads[0]
    tops = sorted((e for e in evs if e[0] in top), key=lambda e: e[1])
    for name in top[1:]:
        assert sum(1 for e in tops if e[0] == name) >= 3, name
    for a, b in zip(tops, tops[1:]):
        assert a[2] <= b[1], (a, b)             # no two overlap
    parents = {"cache": ("engine.admission", "engine.dispatch"),
               "observe": ("engine.observe",),
               "admission": ("engine.admission",),
               "dispatch": ("engine.dispatch",),
               "sample": ("engine.sample",)}
    children = [e for e in evs if e[0] not in top]
    assert {e[0] for e in children} >= {
        "cache.radix_match", "cache.page_alloc", "cache.block_table",
        "cache.cow", "observe.slo", "observe.spans", "observe.flight",
        "observe.gauges", "observe.perf", "observe.probe",
        "dispatch.h2d", "admission.h2d", "sample.fetch"}
    for name, start, end in children:
        inside = [p for p in tops
                  if p[0] in parents[name.partition(".")[0]]
                  and p[1] <= start and end <= p[2]]
        assert inside, (name, start, end)


# -- the loop and the wire ----------------------------------------------------


def test_stream_delivery_counts_sse_chunks_and_ingest_counts_requests():
    from bigdl_tpu.serving.api_server import OpenAIServer

    eng = _engine("slab")
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"

    def post(body):
        return urllib.request.urlopen(urllib.request.Request(
            f"{base}/v1/completions", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}), timeout=120)

    try:
        n_chunks = 0
        for prompt, n in (([1, 2, 3, 4], 6), ([9, 8, 7], 4)):
            with post({"prompt": prompt, "max_tokens": n,
                       "stream": True}) as r:
                lines = [ln for ln in r.read().decode().splitlines()
                         if ln.startswith("data: ")]
            assert lines[-1] == "data: [DONE]"
            n_chunks += len(lines) - 1
            assert len(lines) - 1 == n          # one token a chunk
        with post({"prompt": [5, 6], "max_tokens": 3}) as r:
            assert json.loads(r.read())["usage"]["completion_tokens"] == 3
        summ = eng.registry.summary()
        delivery = summ["bigdl_tpu_stream_delivery_seconds"]
        assert delivery["count"] == n_chunks == 10
        assert 0.0 < delivery["sum"] < 60.0
        ingest = summ['bigdl_tpu_request_phase_seconds{phase="ingest"}']
        assert ingest["count"] == 3             # streamed or not
        assert 0.0 < ingest["sum"] < 60.0
    finally:
        server.shutdown()


def test_outputs_carry_their_push_time():
    eng = _engine("slab")
    t0 = time.perf_counter()
    eng.add_request("p", [1, 2, 3], SamplingParams(max_tokens=3))
    outs = []
    while eng.has_unfinished():
        eng.step()
        outs.extend(eng.get_outputs("p"))
    assert outs and all(
        t0 < o.t_push <= time.perf_counter() for o in outs)
    assert [o.t_push for o in outs] == sorted(o.t_push for o in outs)


# -- kernel names -------------------------------------------------------------


def _pallas_names(fn, *args):
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


@pytest.mark.parametrize("qtype,m,want", [
    ("sym_int4", 4, "qmatmul_gemv_sym_int4"),
    ("nf4", 4, "qmatmul_gemv_nf4"),
    ("sym_int4", 256, "qmatmul_gemm_sym_int4"),
    ("asym_int4", 256, "qmatmul_gemm_asym_int4"),
    ("nf4", 256, "qmatmul_gemm_nf4"),
    ("sym_int8", 256, "qmatmul_gemm_sym_int8"),
])
def test_dequant_matmul_kernels_are_named_by_shape_class_and_qtype(
        qtype, m, want):
    from bigdl_tpu.ops.matmul import q_matmul_pallas_impl
    from bigdl_tpu.ops.quant import quantize

    w = quantize(jnp.ones((256, 256), jnp.float32), qtype)
    x = jnp.ones((m, 256), jnp.bfloat16)
    assert _pallas_names(
        lambda a: q_matmul_pallas_impl(a, w, interpret=True), x) == [want]


def test_the_int4_dtype_layout_names_its_generic_kernel_too():
    from bigdl_tpu.ops.matmul import q_matmul_pallas_impl
    from bigdl_tpu.ops.quant import quantize, to_mxu_layout

    w = to_mxu_layout(quantize(jnp.ones((256, 256), jnp.float32),
                                 "sym_int4"))
    assert w.data.dtype == jnp.int4
    x = jnp.ones((256, 256), jnp.bfloat16)
    assert _pallas_names(
        lambda a: q_matmul_pallas_impl(a, w, interpret=True), x) == [
            "qmatmul_gemm_sym_int4"]


def test_attention_and_moe_kernels_are_named():
    from bigdl_tpu.ops.pallas.decode_attention import \
        decode_attention_pallas
    from bigdl_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention_pallas
    from bigdl_tpu.ops.pallas.prefill_attention import \
        prefill_attention_pallas

    q = jnp.ones((2, 1, 4, 128), jnp.bfloat16)
    k = jnp.ones((2, 128, 2, 128), jnp.bfloat16)
    pos = jnp.array([5, 9], jnp.int32)
    assert _pallas_names(
        lambda: decode_attention_pallas(q, k[None], k[None], pos, 0.088,
                                        interpret=True)
    ) == ["decode_attention"]
    arena = jnp.ones((2, 8, 128, 128), jnp.bfloat16)    # ops/paged.py
    bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
    assert _pallas_names(
        lambda: paged_decode_attention_pallas(q, arena, arena, bt, pos,
                                              0.088, 2, interpret=True)
    ) == ["paged_decode_attention"]
    qp = jnp.ones((1, 128, 4, 128), jnp.bfloat16)
    kp = jnp.ones((1, 128, 2, 128), jnp.bfloat16)
    assert _pallas_names(
        lambda: prefill_attention_pallas(qp, kp, kp,
                                         jnp.zeros((), jnp.int32), 0.088,
                                         interpret=True)
    ) == ["prefill_attention"]


def test_the_ragged_moe_kernel_is_named():
    from bigdl_tpu.ops.pallas import moe_dispatch

    x = jnp.ones((128, 128), jnp.bfloat16)
    w = jnp.ones((2, 128, 128), jnp.bfloat16)
    tile_expert = jnp.zeros((1,), jnp.int32)
    names = _pallas_names(
        lambda: moe_dispatch.ragged_expert_matmul(x, w, tile_expert,
                                                  interpret=True))
    assert names == ["moe_ragged_matmul"]
