"""The start-up account of ``observability/compile_watch``: a first call
of a tracked program is booked by stage (trace, lower, compile or cache
load, memory analysis, first run) from JAX's own monitoring events, to
the innermost program open on the calling thread or to
``fn="untracked"``; a call of a known signature books nothing; with
default settings a signature is lowered once; marks sit on the
process's own clock."""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.observability import MetricsRegistry
from bigdl_tpu.observability import compile_watch as cw
from bigdl_tpu.observability.metrics import default_registry

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
JAX_STAGES = ("trace", "lower", "compile", "cache_load")


@pytest.fixture(scope="module", autouse=True)
def private_cache(tmp_path_factory):
    """A persistent compile cache of this module's own that keeps every
    program (JAX's two thresholds at 0), so that what misses and what
    hits is this module's doing; the run's cache comes back after."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], 0)
    jax.config.update(keys[3], True)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _stages(reg, fn):
    return {k.split('stage="')[1].rstrip('"}'): v
            for k, v in reg.summary().items()
            if k.startswith(cw.STAGE_SECONDS + "{")
            and f'fn="{fn}"' in k}


def _requests(reg, fn):
    return {oc: reg.summary().get(
        f'{cw.CACHE_REQUESTS}{{fn="{fn}",outcome="{oc}"}}', 0)
        for oc in cw.CACHE_OUTCOMES}


def _rows(fn):
    return [r for r in cw.startup_snapshot()["programs"] if r["fn"] == fn]


def _body(scale):
    """A program of this module's own: ``scale`` keeps its cache key
    apart from every other test's."""
    def f(x, y):
        z = jnp.sin(x) * scale + jnp.where(y > 0, y, 0.0)
        return jnp.tanh(z @ z.T)
    return f


@pytest.fixture(scope="module")
def first():
    """One program's first call, then a second call of its signature."""
    reg = MetricsRegistry()
    jf = cw.tracked_jit("stages_first", _body(1.25), registry=reg)
    x = jnp.ones((48, 48))
    t0 = time.perf_counter()
    jf(x, x).block_until_ready()
    wall = time.perf_counter() - t0
    return {"reg": reg, "jf": jf, "x": x, "wall": wall,
            "stages": _stages(reg, "stages_first")}


def test_a_first_call_books_trace_lower_and_compile_under_its_fn(first):
    st = first["stages"]
    assert set(st) == set(cw.STAGES)        # every series from compile 1
    assert st["trace"] > 0 and st["lower"] > 0 and st["compile"] > 0
    assert st["cache_load"] == 0 and st["memory_analysis"] == 0
    assert _requests(first["reg"], "stages_first") == {"hit": 0, "miss": 1}
    # mirrored: the default registry holds the same program
    assert _stages(default_registry(), "stages_first")["compile"] \
        >= st["compile"]


def test_a_first_call_is_a_row_of_the_timeline_and_of_the_table(first):
    row = _rows("stages_first")[-1]
    assert row["cache"] == "miss" and row["signature"].count("[48,48]") == 2
    assert row["thread"] == threading.current_thread().name
    assert 0 < row["t0"] < row["t1"] <= cw.process_age_s()
    assert row["t1"] - row["t0"] <= first["wall"]
    sig = cw.compile_table()["stages_first"]["signatures"][-1]
    assert sig["stages"] == row["stages"]
    # total_s keeps its meaning: the call's wall
    assert sum(sig["stages"].values()) == pytest.approx(
        sig["seconds"], abs=1e-4)


def test_booked_stages_never_exceed_the_first_calls_wall(first):
    st = first["stages"]
    assert sum(st[k] for k in JAX_STAGES) <= first["wall"]
    assert sum(st.values()) <= first["wall"]
    for ent in cw.compile_table().values():
        for sig in ent["signatures"]:
            inside = sum(v for k, v in sig.get("stages", {}).items()
                         if k in JAX_STAGES)
            assert inside <= sig["seconds"] + 1e-5, sig


def test_a_known_signature_books_nothing_and_flattens_once(first):
    jf, reg, x = first["jf"], first["reg"], first["x"]
    flattens = []
    real = jf._flatten
    jf._flatten = lambda tree: flattens.append(1) or real(tree)
    before = (reg.render(), cw.startup_snapshot()["programs"],
              cw.startup_snapshot()["marks"])
    try:
        jf(x, x).block_until_ready()
    finally:
        jf._flatten = real
    assert len(flattens) == 1
    assert reg.render() == before[0]
    assert cw.startup_snapshot()["programs"] == before[1]
    assert cw.startup_snapshot()["marks"] == before[2]
    assert jf.compiles == 1


def test_after_clear_caches_the_program_loads_from_the_cache(first):
    jax.clear_caches()
    reg = MetricsRegistry()
    again = cw.tracked_jit("stages_first", _body(1.25), registry=reg)
    again(first["x"], first["x"]).block_until_ready()
    st = _stages(reg, "stages_first")
    assert st["cache_load"] > 0 and st["compile"] == 0
    assert st["trace"] > 0 and st["lower"] > 0
    assert _requests(reg, "stages_first") == {"hit": 1, "miss": 0}
    assert _rows("stages_first")[-1]["cache"] == "hit"


def test_a_bare_jit_lands_in_untracked():
    x = jnp.ones((40, 40))      # an eager program of its own
    before = _stages(default_registry(), cw.UNTRACKED)
    req = _requests(default_registry(), cw.UNTRACKED)
    rows = len(cw.startup_snapshot()["programs"])
    jax.jit(_body(2.5))(x, x)
    after = _stages(default_registry(), cw.UNTRACKED)
    for st in ("trace", "lower", "compile"):
        assert after[st] > before.get(st, 0.0), st
    assert _requests(default_registry(), cw.UNTRACKED)["miss"] \
        == req["miss"] + 1
    assert len(cw.startup_snapshot()["programs"]) == rows   # no row


def test_nested_first_calls_book_to_the_innermost():
    reg = MetricsRegistry()
    inner = cw.tracked_jit("stages_inner", lambda x: jnp.cos(x) * 3.75,
                           registry=reg)
    outer = cw.tracked_jit(
        "stages_outer", lambda x: jnp.tanh(inner(x) @ inner(x).T),
        registry=reg)
    t0 = time.perf_counter()
    outer(jnp.ones((40, 40))).block_until_ready()
    wall = time.perf_counter() - t0
    i, o = _stages(reg, "stages_inner"), _stages(reg, "stages_outer")
    # the inner program is traced inside the outer's trace and never
    # lowered or compiled on its own
    assert i["trace"] > 0 and i["lower"] == 0 and i["compile"] == 0
    assert o["trace"] > 0 and o["lower"] > 0 and o["compile"] > 0
    assert _requests(reg, "stages_inner") == {"hit": 0, "miss": 0}
    assert _requests(reg, "stages_outer") == {"hit": 0, "miss": 1}
    # exclusive: the inner's wall comes off the outer's trace, so both
    # programs together never exceed the one wall
    assert sum(i.values()) + sum(o.values()) <= wall
    ri, ro = _rows("stages_inner")[-1], _rows("stages_outer")[-1]
    assert ro["t0"] <= ri["t0"] <= ri["t1"] <= ro["t1"]


def _count_lowerings(call):
    """How many ``jaxpr_to_mlir_module_duration`` events ``call``
    fires."""
    seen = []

    def on(event, seconds, **kw):
        if event == LOWER_EVENT:
            seen.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        call()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    return len(seen)


def _committed(n):
    # committed arguments: the AOT capture's placeholders carry no
    # sharding, so its lowering cannot be the traced call's
    return jax.device_put(jnp.ones((n, n)), jax.devices()[0])


def test_default_settings_lower_a_signature_once(monkeypatch):
    monkeypatch.delenv(cw.COMPILE_MEMORY_ENV, raising=False)
    assert cw.memory_capture_enabled() is False
    reg = MetricsRegistry()
    jf = cw.tracked_jit("stages_once", _body(4.5), registry=reg)
    x = _committed(56)
    assert _count_lowerings(lambda: jf(x, x).block_until_ready()) == 1
    assert _stages(reg, "stages_once")["memory_analysis"] == 0
    sig = cw.compile_table()["stages_once"]["signatures"][-1]
    assert "memory" not in sig and sig["stages"]["memory_analysis"] == 0
    assert set(sig["stages"]) == set(cw.STAGES)


def test_the_capture_is_booked_as_memory_analysis_when_asked(monkeypatch):
    monkeypatch.setenv(cw.COMPILE_MEMORY_ENV, "1")
    reg = MetricsRegistry()
    jf = cw.tracked_jit("stages_capture", _body(5.5), registry=reg)
    x = _committed(56)
    lowerings = _count_lowerings(lambda: jf(x, x).block_until_ready())
    st = _stages(reg, "stages_capture")
    assert st["memory_analysis"] > 0
    # what the capture lowers and compiles is its own stage's, not
    # booked a second time under lower / compile
    assert _requests(reg, "stages_capture") == {"hit": 0, "miss": 1}
    sig = cw.compile_table()["stages_capture"]["signatures"][-1]
    assert sig["memory"]["argument_bytes"] == 2 * 56 * 56 * 4
    assert sig["stages"]["memory_analysis"] > 0
    # total_s keeps its meaning: the capture is not in it
    assert sig["seconds"] == pytest.approx(
        sum(v for k, v in sig["stages"].items()
            if k != "memory_analysis"), abs=1e-4)
    row = _rows("stages_capture")[-1]
    assert row["t1"] - row["t0"] >= sig["seconds"] \
        + sig["stages"]["memory_analysis"] - 1e-4
    assert lowerings in (1, 2)      # 2 where the capture's key differs


def test_a_first_call_that_raises_books_nothing_and_closes_its_frame():
    reg = MetricsRegistry()

    def bad(x):
        raise RuntimeError("no trace")

    jf = cw.tracked_jit("stages_bad", bad, registry=reg)
    with pytest.raises(RuntimeError, match="no trace"):
        jf(jnp.ones((8,)))
    assert _stages(reg, "stages_bad") == {} and jf.compiles == 0
    assert cw.compiles_in_progress() == 0
    # the thread's stack is back at its unowned frame: the next bare jit
    # is untracked, the next tracked program its own
    x = jnp.ones((24,))
    before = _requests(default_registry(), cw.UNTRACKED)["miss"]
    jax.jit(lambda x: x * 6.5)(x)
    assert _requests(default_registry(), cw.UNTRACKED)["miss"] == before + 1


def test_a_first_call_on_another_thread_is_that_threads(first):
    reg = MetricsRegistry()
    jf = cw.tracked_jit("stages_thread", _body(7.5), registry=reg)
    x = first["x"]
    t = threading.Thread(target=lambda: jf(x, x).block_until_ready(),
                         name="stages-worker")
    untracked = _stages(default_registry(), cw.UNTRACKED)
    t.start()
    t.join()
    assert _rows("stages_thread")[-1]["thread"] == "stages-worker"
    assert _stages(reg, "stages_thread")["compile"] > 0
    # nothing of it leaked to this thread's unowned events
    assert _stages(default_registry(), cw.UNTRACKED) == untracked


def test_the_timeline_is_bounded():
    assert cw.MAX_TIMELINE_ROWS >= 64
    assert len(cw.startup_snapshot()["programs"]) <= cw.MAX_TIMELINE_ROWS
    assert cw.startup_snapshot(programs=False).keys() == {
        "process_age_s", "clock_source", "marks"}


def test_process_age_comes_from_the_process_start():
    a = cw.process_age_s()
    time.sleep(0.01)
    b = cw.process_age_s()
    assert 0 < a < b < a + 5.0
    assert cw.process_age_s(time.perf_counter() - 1.0) == pytest.approx(
        cw.process_age_s() - 1.0, abs=0.05)
    assert cw.PROCESS_CLOCK_SOURCE in ("proc_stat", "import")
    if cw.PROCESS_CLOCK_SOURCE == "proc_stat":
        # the interpreter and every import came before this module's
        assert a > 0.05


@pytest.mark.parametrize("name", [m for m in cw.MARKS
                                  if m != "last_compile_end"])
def test_a_mark_is_set_once_a_registry(name):
    reg = MetricsRegistry()
    key = f'{cw.MARK_SECONDS}{{mark="{name}"}}'
    assert key not in reg.summary()         # absent until reached
    first_age = cw.mark(name, reg)
    time.sleep(0.002)
    cw.mark(name, reg)
    assert reg.summary()[key] == first_age
    # process-wide, the first to reach it stands (rounded to the us)
    assert cw.startup_snapshot()["marks"][name] <= first_age + 1e-6


def test_last_compile_end_moves_with_every_first_call():
    reg = MetricsRegistry()
    key = f'{cw.MARK_SECONDS}{{mark="last_compile_end"}}'
    jf = cw.tracked_jit("stages_marks", _body(8.5), registry=reg)
    jf(jnp.ones((24, 24)), jnp.ones((24, 24)))
    one = reg.summary()[key]
    jf(jnp.ones((32, 32)), jnp.ones((32, 32)))
    two = reg.summary()[key]
    assert 0 < one < two <= cw.process_age_s()
    assert two == pytest.approx(_rows("stages_marks")[-1]["t1"], abs=1e-5)


def test_the_families_render_from_scrape_1():
    reg = MetricsRegistry()
    cw.declare_startup_metrics(reg)
    text = reg.render()
    for fam, kind in ((cw.STAGE_SECONDS, "counter"),
                      (cw.CACHE_REQUESTS, "counter"),
                      (cw.MARK_SECONDS, "gauge")):
        assert f"# TYPE {fam} {kind}" in text
        assert f"# HELP {fam} " in text
