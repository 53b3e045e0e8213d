"""Compile telemetry: ``tracked_jit`` wrappers around every ``jax.jit``.

The hot paths in this stack are configuration-sensitive by design —
prefill compiles per (prompt-length bucket, kv dtype), the engine keeps
per-shape sampler executables, speculative rounds compile per gamma.
That is the intended cost model ("compile few, reuse forever"), but it
also means a mis-bucketed client or a dtype knob flipped mid-flight can
silently recompile every step and nothing in steady-state latency
metrics says why. ``tracked_jit(name, fn, ...)`` is ``jax.jit`` plus an
accounting layer:

- a per-wrapper signature set (pytree structure + abstract shape/dtype
  of every leaf) detects first-call-for-a-signature, i.e. a compile;
- each compile increments ``bigdl_tpu_jit_compiles_total{fn=name}`` and
  observes the first-call wall time (trace + lower + compile or cache
  load + first dispatch) into ``bigdl_tpu_jit_compile_seconds{fn=name}``;
- the process-wide compile table (``compile_table()``) keeps per-name
  counts, cumulative seconds, and the most recent signatures — embedded
  in postmortem dumps (observability/flight.py) and BENCH json;
- crossing the recompile-storm threshold (``warn_threshold=`` or
  ``$BIGDL_TPU_RECOMPILE_WARN``, default 8 compiles per name) logs one
  warning and flags the table entry;
- each first call is taken apart BY STAGE from inside (the start-up
  account, below): ``bigdl_tpu_jit_stage_seconds_total{fn, stage}`` and
  ``bigdl_tpu_compile_cache_requests_total{fn, outcome}``, a span
  ``compile.<fn>`` on the profiler's clock and a row of the start-up
  timeline (``startup_snapshot()``; ``/v1/stats`` ``startup``);
- with ``$BIGDL_TPU_COMPILE_MEMORY=1`` (default OFF since PR 55) each
  compile also captures the executable's ``compiled.memory_analysis()``
  (temp / argument / output / generated-code bytes) next to its
  seconds, and the table keeps the per-name ``peak_temp_bytes`` — the
  scratch HBM a jitted fn needs on top of its operands. Capture goes
  through jax's AOT path (``lower(...).compile()`` on abstract
  placeholder shapes): where the placeholders' key differs from the
  traced call's (committed arguments) that is a SECOND lowering and a
  second compile or cache load of the program, on the way to
  readiness, which is why nothing pays it unasked. What it spends is
  booked as ``stage="memory_analysis"``.

The start-up account. One listener each for JAX's duration and count
events (``jax.monitoring``), registered once a process when the first
``TrackedJit`` is made. ``TrackedJit.__call__``'s first-call branch,
and only it, opens a frame on a thread-local stack; an event that
arrives on that thread is booked to the innermost frame's ``fn``, an
event with no frame open to ``fn="untracked"`` (a model's own
``jax.jit``, eager ``convert_element_type``s, the weights' jitted
builders; the default registry only). Which event feeds which stage
(checked on jax 0.9.0):

- ``/jax/core/compile/jaxpr_trace_duration``            -> ``trace``
- ``/jax/core/compile/jaxpr_to_mlir_module_duration``   -> ``lower``
- ``/jax/core/compile/backend_compile_duration``        -> ``compile``
  or ``cache_load``. JAX brackets ALL of ``compile_or_get_cached`` with
  it: hashing the module for its key, the cache read, then either the
  deserialised executable or the compile and the cache write. So the
  bracket alone cannot say which it was. ``/jax/compilation_cache/
  cache_hits`` (a count event, fired inside the bracket and before it
  closes) does: a bracket that saw it is a ``hit`` and the WHOLE of it
  goes to ``cache_load``; one that did not is a ``miss`` (the cache
  off, the program under JAX's thresholds, or not found) and the whole
  of it goes to ``compile``. A hit's retrieval is thereby never in
  ``compile``. (``/jax/compilation_cache/cache_misses`` fires only when
  an entry is WRITTEN, so it cannot count the misses;
  ``cache_retrieval_time_sec`` is the read alone, less than the
  bracket by the hashing.)
- ``first_run``: the first call's wall less everything booked inside
  it — dispatch, argument transfer, what JAX reports under no event;
- ``memory_analysis``: the wall of the AOT capture, when it is on; the
  events inside it are swallowed, not booked twice.

JAX reports a duration when it ENDS, and traces nest (every ``jnp``
function is a ``jit`` whose trace fires its own event inside the
program's), so a stage is booked EXCLUSIVE time: an event is charged
its duration less the closed intervals it contains, and a nested first
call's wall comes off the frame around it. The stages of a first call
therefore add up to its wall, and all programs to the time the process
stopped to compile.

Start-up marks (``mark()``): ``bigdl_tpu_startup_mark_seconds{mark}``,
seconds since the PROCESS started (``process_age_s()``), on the same
clock as the timeline's rows.

Detection is signature-based rather than hooking XLA: it is exact for
the wrappers' own cache (jax.jit keys its trace cache on the same
abstract signature) and costs one tree_flatten per call.

Stdlib-only at import time (tests/test_observability.py enforces it):
jax is imported lazily inside ``tracked_jit``, which only ever runs from
modules that already depend on jax.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

DEFAULT_RECOMPILE_WARN = 8
# signatures kept per name in the compile table (newest last); the
# counters keep counting past this bound
MAX_SIGNATURES_PER_NAME = 32
COMPILE_MEMORY_ENV = "BIGDL_TPU_COMPILE_MEMORY"


# rows kept in the start-up timeline (newest last), all names together
MAX_TIMELINE_ROWS = 256
# closed intervals a frame remembers: the trace of a whole model closes
# over this many nested ones at the most
MAX_FRAME_SPANS = 4096

UNTRACKED = "untracked"
STAGES = ("trace", "lower", "compile", "cache_load", "memory_analysis",
          "first_run")
CACHE_OUTCOMES = ("hit", "miss")
MARKS = ("engine_init_begin", "engine_init_end", "listening",
         "first_request", "first_token", "last_compile_end")
STAGE_SECONDS = "bigdl_tpu_jit_stage_seconds_total"
CACHE_REQUESTS = "bigdl_tpu_compile_cache_requests_total"
MARK_SECONDS = "bigdl_tpu_startup_mark_seconds"

# jax.monitoring's names (jax/_src/dispatch.py, compiler.py)
_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def memory_capture_enabled() -> bool:
    """Whether per-compile memory_analysis capture is on (default NO
    since PR 55: it can cost a second lowering and compile of every
    program; ``$BIGDL_TPU_COMPILE_MEMORY`` in {1, true, on, yes}
    enables)."""
    return os.environ.get(COMPILE_MEMORY_ENV, "0").strip().lower() \
        in ("1", "true", "on", "yes")

_lock = threading.Lock()
_table: Dict[str, Dict[str, Any]] = {}

# per-name DISPATCH counts (every call of a tracked executable, compile
# or cache hit). The resident-decode work (ISSUE 14) is measured in
# host dispatches per engine step; this table is how tests assert
# "exactly one" without profiling the runtime.
_dispatch_lock = threading.Lock()
_dispatches: Dict[str, int] = {}


def _count_dispatch(name: str) -> None:
    with _dispatch_lock:
        _dispatches[name] = _dispatches.get(name, 0) + 1


def dispatch_table() -> Dict[str, int]:
    """Snapshot of per-name tracked-jit dispatch counts since process
    start (or the last ``reset_dispatch_table()``). One entry per
    tracked executable name; every __call__ counts, compiles included."""
    with _dispatch_lock:
        return dict(_dispatches)


def reset_dispatch_table() -> None:
    """Zero the per-name dispatch counters (tests bracket an engine
    step with reset + dispatch_table() to count its host dispatches)."""
    with _dispatch_lock:
        _dispatches.clear()

# first-call-for-a-signature compiles currently executing, process-wide.
# A compile blocks the engine's step loop for seconds-to-minutes (real
# TPU lowerings far exceed any sane wedge threshold), during which the
# step heartbeat goes stale exactly like a genuine hang — liveness
# checks (api_server /health) consult this to tell the two apart.
_inflight_lock = threading.Lock()
_compiles_inflight = 0


def compiles_in_progress() -> int:
    """Number of tracked first-call compiles executing right now.
    Nonzero means a stale step heartbeat is the compiler working, not a
    wedged replica. (A compile that itself hangs forever is reported as
    'busy' rather than 'wedged' — the supervisor's spawn timeout is the
    backstop for that.)"""
    with _inflight_lock:
        return _compiles_inflight


class _CompileInFlight:
    """Context manager bracketing one first-call compile."""

    def __enter__(self):
        global _compiles_inflight
        with _inflight_lock:
            _compiles_inflight += 1
        return self

    def __exit__(self, *exc):
        global _compiles_inflight
        with _inflight_lock:
            _compiles_inflight -= 1
        return False


# -- the start-up account ----------------------------------------------------


def _process_start() -> Tuple[float, str]:
    """``time.perf_counter()`` at the moment the process started, and
    where that came from. Linux: ``CLOCK_BOOTTIME`` now less the start
    time in ``/proc/self/stat`` (field 22, clock ticks since boot: a
    resolution of 1 / ``SC_CLK_TCK``, 10 ms as a rule). Elsewhere, or
    where the two disagree, the moment this module was imported (with
    ``bigdl_tpu``: the interpreter's own start is then not counted)."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat", "rb") as f:
            # the command's name may hold spaces: fields count from ")"
            ticks = float(f.read().rsplit(b")", 1)[1].split()[19])
        boot_now = time.clock_gettime(time.CLOCK_BOOTTIME)
        age = boot_now - ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < boot_now:
            return now - age, "proc_stat"
    except Exception:
        pass
    return now, "import"


_PROCESS_T0, PROCESS_CLOCK_SOURCE = _process_start()


def process_age_s(t: Optional[float] = None) -> float:
    """Seconds since the process started, at ``time.perf_counter()``
    reading ``t`` (now when None): the one clock of the start-up marks
    and of the timeline's ``t0`` / ``t1``."""
    return (time.perf_counter() if t is None else t) - _PROCESS_T0


class _Frame:
    """What one first call (or, with ``fn`` UNTRACKED, one thread's
    unowned events) has booked so far. ``spans`` holds the closed
    top-level intervals ``(end, duration)`` in the order they ended."""

    __slots__ = ("fn", "stages", "cache", "spans", "hit", "capturing")

    def __init__(self, fn: str):
        self.fn = fn
        self.stages: Dict[str, float] = {}
        self.cache: Dict[str, int] = {}
        self.spans: List[Tuple[float, float]] = []
        self.hit = False            # a cache hit inside the open bracket
        self.capturing = False      # inside the AOT memory capture

    def close_span(self, end: float, seconds: float) -> float:
        """Put ``[end - seconds, end]`` among the closed intervals and
        return its EXCLUSIVE seconds: JAX reports inner events first,
        so every interval that ended after this one began lies inside
        it and has been booked already."""
        start, inner = end - seconds, 0.0
        spans = self.spans
        while spans and spans[-1][0] > start:
            inner += spans.pop()[1]
        spans.append((end, seconds))
        del spans[:-MAX_FRAME_SPANS]
        return max(seconds - inner, 0.0)


_tls = threading.local()
_listeners_lock = threading.Lock()
_listeners_installed = False
_timeline_lock = threading.Lock()
_timeline: "collections.deque[Dict[str, Any]]" = collections.deque(
    maxlen=MAX_TIMELINE_ROWS)
_marks_lock = threading.Lock()
_marks: Dict[str, float] = {}


def _frames() -> List[_Frame]:
    try:
        return _tls.frames
    except AttributeError:
        _tls.frames = [_Frame(UNTRACKED)]   # [0]: the thread's unowned
        return _tls.frames


def _account_families(reg):
    return (
        reg.counter(
            STAGE_SECONDS,
            "Seconds of first calls (one per new signature of a tracked "
            "executable) by stage, exclusive: trace, lower, compile (a "
            "backend compile: persistent-cache miss), cache_load (the "
            "same bracket on a hit), memory_analysis (the AOT capture, "
            "BIGDL_TPU_COMPILE_MEMORY=1 only), first_run (the call's "
            "wall less the rest). fn=untracked: JAX compile events on a "
            "thread with no tracked first call open (default registry "
            "only).", labelnames=("fn", "stage")),
        reg.counter(
            CACHE_REQUESTS,
            "Programs handed to the backend compiler by what the "
            "persistent compile cache did: outcome=hit loaded, "
            "outcome=miss compiled (cache off, under JAX's thresholds, "
            "or not found).", labelnames=("fn", "outcome")))


def _registries(registry=None) -> list:
    """The default registry, and ``registry`` where it is another."""
    from bigdl_tpu.observability.metrics import default_registry

    regs = [default_registry()]
    if registry is not None and registry is not regs[0]:
        regs.append(registry)
    return regs


def _book(fn: str, stages: Dict[str, float], cache: Dict[str, int],
          registry=None) -> None:
    """Add seconds by stage and cache outcomes to the default registry
    and to ``registry`` (a zero renders the series)."""
    for reg in _registries(registry):
        seconds, requests = _account_families(reg)
        for st, v in stages.items():
            seconds.labels(fn, st).inc(v)
        for oc, n in cache.items():
            requests.labels(fn, oc).inc(n)


def _on_duration(event: str, seconds: float, **_kw) -> None:
    stage = _STAGE_OF_EVENT.get(event)
    if stage is None:
        return
    frame = _frames()[-1]
    if frame.capturing:
        return
    own = frame.close_span(time.perf_counter(), seconds)
    cache = None
    if stage == "compile":          # the bracket: a load where it hit
        cache = "hit" if frame.hit else "miss"
        stage = "cache_load" if frame.hit else "compile"
        frame.hit = False
    if frame.fn == UNTRACKED:
        try:
            _book(UNTRACKED, {stage: own}, {cache: 1} if cache else {})
        except Exception:
            pass            # telemetry must never break a compile
        return
    frame.stages[stage] = frame.stages.get(stage, 0.0) + own
    if cache:
        frame.cache[cache] = frame.cache.get(cache, 0) + 1


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _frames()[-1].hit = True


def _install_listeners() -> None:
    """Register the two listeners, once a process."""
    global _listeners_installed
    with _listeners_lock:
        if _listeners_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listeners_installed = True


def declare_startup_metrics(registry) -> None:
    """Create the account's families in ``registry`` so that they
    render (HELP and TYPE) from scrape 1; a program's series appear
    with its first compile, a mark's when it is reached."""
    _account_families(registry)
    _mark_family(registry)


def _mark_family(reg):
    return reg.gauge(
        MARK_SECONDS,
        "Seconds since the process started (CLOCK_BOOTTIME less "
        "/proc/self/stat's start time, 10 ms; else since bigdl_tpu was "
        "imported) at which it reached a mark: engine_init_begin, "
        "engine_init_end, listening, first_request, first_token (each "
        "set once) and last_compile_end (moved by every first call of "
        "a tracked executable).", labelnames=("mark",))


def mark(name: str, registry=None, t: Optional[float] = None) -> float:
    """Set start-up mark ``name`` to the process's age (at
    ``perf_counter()`` reading ``t``, else now) in the default registry
    and in ``registry``. Every mark but ``last_compile_end`` is set
    once a registry: the first to reach it stands. Returns the age."""
    age = process_age_s(t)
    moves = name == "last_compile_end"
    with _marks_lock:
        if moves or name not in _marks:
            _marks[name] = age
    for reg in _registries(registry):
        child = _mark_family(reg).labels(name)
        if moves or not child.value:
            child.set(age)
    return age


def startup_snapshot(programs: bool = True) -> Dict[str, Any]:
    """JSON-ready start-up account (``/v1/stats`` ``startup``, postmortem
    dumps): the process's age, the marks it has reached (the first time
    each was, process-wide) and, with ``programs``, the timeline of
    first calls, oldest first: ``{fn, signature, t0, t1, stages, cache,
    thread}`` with ``t0`` / ``t1`` in seconds since the process
    started, on ``time.perf_counter()``."""
    with _marks_lock:
        marks = {k: round(v, 6) for k, v in _marks.items()}
    out = {"process_age_s": round(process_age_s(), 6),
           "clock_source": PROCESS_CLOCK_SOURCE, "marks": marks}
    if programs:
        with _timeline_lock:
            out["programs"] = [dict(r, stages=dict(r["stages"]))
                               for r in _timeline]
    return out


def resolve_recompile_threshold(value: Optional[object] = None) -> int:
    """The recompile-storm warning threshold: explicit value, else
    ``$BIGDL_TPU_RECOMPILE_WARN``, else the default. Raises ValueError
    on a non-positive or non-integer setting (utils/env_check.py
    surfaces this for the env var)."""
    if value is None:
        value = os.environ.get("BIGDL_TPU_RECOMPILE_WARN")
    if value is None or value == "":
        return DEFAULT_RECOMPILE_WARN
    try:
        n = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"recompile threshold must be a positive integer, got "
            f"{value!r}")
    if n <= 0:
        raise ValueError(
            f"recompile threshold must be a positive integer, got {n}")
    return n


def _leaf_sig(x: Any) -> Tuple:
    """Hashable abstract signature of one DYNAMIC (traced) pytree leaf,
    matching how jax's trace cache keys it: arrays by (dtype, shape);
    python bool/int/float/complex by TYPE ONLY (jax traces them as
    weak-typed 0-d arrays, so the value does not recompile — a beam
    step counter t=0,1,2,... reuses one executable); anything else by
    type (+hash when it has one)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (str(dtype), tuple(shape))
    if isinstance(x, (bool, int, float, complex)):
        return (type(x).__name__,)
    try:
        return (type(x).__name__, hash(x))
    except TypeError:
        return (type(x).__name__,)


def _static_sig(x: Any) -> Tuple:
    """Signature of a static_argnums/static_argnames argument: keyed by
    VALUE — that is what jax keys compiles on for statics."""
    try:
        hash(x)
        return (type(x).__name__, x)
    except TypeError:
        return (type(x).__name__, repr(x))


def _sig_str(sig: Tuple) -> str:
    """Compact human-readable form for the compile table (arrays as
    'f32[2,8]'-style, statics as key=value)."""
    _treedef, leaves, statics = sig
    parts: List[str] = []
    for leaf in leaves:
        if (len(leaf) == 2 and isinstance(leaf[1], tuple)
                and all(isinstance(d, int) for d in leaf[1])):
            parts.append(f"{leaf[0]}[{','.join(map(str, leaf[1]))}]")
        else:
            parts.append(repr(leaf[1]) if len(leaf) > 1 else leaf[0])
    for key, val in statics:
        parts.append(f"{key}={val[1]!r}")
    return "(" + ", ".join(parts) + ")"


class TrackedJit:
    """A jax.jit-compiled callable with compile accounting.

    Calls pass straight through to the jitted function; the only
    per-call overhead on the cache-hit path is one tree_flatten of the
    arguments. Unknown attributes (``lower``, ``clear_cache``, ...)
    forward to the underlying jitted callable.
    """

    def __init__(self, name: str, fn, registry=None,
                 warn_threshold: Optional[int] = None, **jit_kwargs):
        import jax

        self.name = name
        self._fn = fn
        self._jitted = jax.jit(fn, **jit_kwargs)
        self._flatten = jax.tree_util.tree_flatten
        self._registry = registry
        try:
            self._warn_threshold = resolve_recompile_threshold(
                warn_threshold)
        except ValueError:
            logger.warning(
                "invalid BIGDL_TPU_RECOMPILE_WARN=%r; using default %d",
                os.environ.get("BIGDL_TPU_RECOMPILE_WARN"),
                DEFAULT_RECOMPILE_WARN)
            self._warn_threshold = DEFAULT_RECOMPILE_WARN
        sa = jit_kwargs.get("static_argnums", ())
        self._static_argnums = (sa,) if isinstance(sa, int) else tuple(sa)
        sn = jit_kwargs.get("static_argnames", ())
        self._static_argnames = (sn,) if isinstance(sn, str) else tuple(sn)
        self._seen: set = set()
        self._seen_lock = threading.Lock()
        _install_listeners()

    # -- call path -----------------------------------------------------------

    def _signature(self, args, kwargs) -> Tuple:
        """Mirror jax's compile key: static args by value, everything
        else by pytree structure + abstract leaf signature."""
        statics: List[Tuple] = []
        dyn_args = []
        for i, a in enumerate(args):
            if i in self._static_argnums:
                statics.append((i, _static_sig(a)))
            else:
                dyn_args.append(a)
        dyn_kwargs = {}
        for k, v in kwargs.items():
            if k in self._static_argnames:
                statics.append((k, _static_sig(v)))
            else:
                dyn_kwargs[k] = v
        leaves, treedef = self._flatten((dyn_args, dyn_kwargs))
        return (treedef, tuple(_leaf_sig(x) for x in leaves),
                tuple(statics))

    def __call__(self, *args, **kwargs):
        _count_dispatch(self.name)
        try:
            sig = self._signature(args, kwargs)
            with self._seen_lock:
                hit = sig in self._seen
        except Exception:
            # unhashable exotic leaf: telemetry must never break the
            # compiled path — run untracked
            return self._jitted(*args, **kwargs)
        if hit:
            return self._jitted(*args, **kwargs)
        # placeholders must be built BEFORE the call: donate_argnums
        # deletes input buffers during it
        placeholders = self._placeholders(args, kwargs)
        from bigdl_tpu.utils.profiling import annotate

        frames, frame = _frames(), _Frame(self.name)
        # the span makes a compile inside an engine step the innermost
        # program span of the phase that holds it
        with _CompileInFlight(), annotate("compile." + self.name):
            frames.append(frame)
            t0 = time.perf_counter()
            try:
                out = self._jitted(*args, **kwargs)
                # dispatch-return time on a FIRST call is dominated by
                # the synchronous trace+compile — that is exactly what
                # the compile table records, so no device fence here
                t1 = time.perf_counter()  # graftlint: disable=jax-unsynced-timing
                with self._seen_lock:
                    self._seen.add(sig)
                # the AOT memory_analysis (opt-in) may compile the
                # signature a second time — keep it inside the in-flight
                # bracket so the heartbeat stays excused for it too
                frame.capturing = True
                memory = self._memory_analysis(placeholders)
            finally:
                frames.pop()
                t2 = time.perf_counter()
                # one closed interval for whatever is open around it
                # (the first call's wall: see t1 above)
                frames[-1].close_span(t2, t2 - t0)  # graftlint: disable=jax-unsynced-timing
            if placeholders is not None:
                frame.stages["memory_analysis"] = t2 - t1
            self._record_compile(sig, frame, t0, t1, t2, memory)
        return out

    def __getattr__(self, item):
        return getattr(self._jitted, item)

    # -- memory analysis -----------------------------------------------------

    def _placeholders(self, args, kwargs):
        """(args, kwargs) with every dynamic array leaf replaced by a
        ShapeDtypeStruct — abstract inputs for the AOT lowering, safe
        against donated buffers. Statics keep their real values (jax
        keys compiles on them); non-array dynamic leaves (python
        scalars) pass through, matching how the traced call saw them.
        None when capture is disabled."""
        if not memory_capture_enabled():
            return None
        try:
            import jax

            def abstract(x):
                shape = getattr(x, "shape", None)
                dtype = getattr(x, "dtype", None)
                if shape is not None and dtype is not None:
                    return jax.ShapeDtypeStruct(tuple(shape), dtype)
                return x

            ph_args = tuple(
                a if i in self._static_argnums
                else jax.tree_util.tree_map(abstract, a)
                for i, a in enumerate(args))
            ph_kwargs = {
                k: v if k in self._static_argnames
                else jax.tree_util.tree_map(abstract, v)
                for k, v in kwargs.items()}
            return (ph_args, ph_kwargs)
        except Exception:
            return None

    def _memory_analysis(self, placeholders) -> Optional[Dict[str, int]]:
        """Best-effort CompiledMemoryStats for one signature via the
        AOT path (where its key differs from the traced call's, the
        capture pays one extra lowering and XLA compile or cache load —
        see module docstring). Never raises."""
        if placeholders is None:
            return None
        try:
            ph_args, ph_kwargs = placeholders
            stats = self._jitted.lower(
                *ph_args, **ph_kwargs).compile().memory_analysis()
            if stats is None:
                return None
            return {
                "temp_bytes": int(stats.temp_size_in_bytes),
                "argument_bytes": int(stats.argument_size_in_bytes),
                "output_bytes": int(stats.output_size_in_bytes),
                "alias_bytes": int(stats.alias_size_in_bytes),
                "generated_code_bytes": int(
                    stats.generated_code_size_in_bytes),
            }
        except Exception:
            return None

    # -- accounting ----------------------------------------------------------

    @property
    def compiles(self) -> int:
        with self._seen_lock:
            return len(self._seen)

    def _record_compile(self, sig: Tuple, frame: _Frame, t0: float,
                        t1: float, t2: float,
                        memory: Optional[Dict[str, int]] = None) -> None:
        """Book one first call: ``frame`` holds what JAX reported inside
        it; on ``perf_counter()`` it began at ``t0``, returned at ``t1``
        and had cost everything (the capture included) at ``t2``."""
        seconds = t1 - t0
        # every stage and outcome, so that a program's series all
        # render from its first compile on
        stages = {st: frame.stages.get(st, 0.0) for st in STAGES}
        stages["first_run"] = max(
            seconds - sum(d for _, d in frame.spans), 0.0)
        cache = {oc: frame.cache.get(oc, 0) for oc in CACHE_OUTCOMES}
        try:
            self._observe_metrics(seconds)
            _book(self.name, stages, cache, self._registry)
            mark("last_compile_end", self._registry, t2)
        except Exception:
            pass
        sig_str = _sig_str(sig)
        rounded = {k: round(v, 6) for k, v in stages.items()}
        with _timeline_lock:
            _timeline.append({
                "fn": self.name, "signature": sig_str,
                "t0": round(process_age_s(t0), 6),
                "t1": round(process_age_s(t2), 6),
                "stages": rounded,
                "cache": ("hit" if cache["hit"] and not cache["miss"]
                          else "miss"),
                "thread": threading.current_thread().name})
        storm = False
        with _lock:
            ent = _table.setdefault(self.name, {
                "compiles": 0, "total_s": 0.0, "signatures": [],
                "last_compile_ts": 0.0, "storm": False,
                "peak_temp_bytes": 0})
            ent.setdefault("peak_temp_bytes", 0)
            ent["compiles"] += 1
            ent["total_s"] += seconds
            ent["last_compile_ts"] = time.time()
            sigs = ent["signatures"]
            row = {"signature": sig_str,
                   "seconds": round(seconds, 6),
                   "stages": dict(rounded)}
            if memory is not None:
                row["memory"] = dict(memory)
                ent["peak_temp_bytes"] = max(
                    ent["peak_temp_bytes"], memory.get("temp_bytes", 0))
            sigs.append(row)
            del sigs[:-MAX_SIGNATURES_PER_NAME]
            if ent["compiles"] >= self._warn_threshold \
                    and not ent["storm"]:
                ent["storm"] = True
                storm = True
        if storm:
            logger.warning(
                "recompile storm: %r compiled %d times (threshold %d) — "
                "check for unbucketed shapes or per-call dtype churn",
                self.name, self._warn_threshold, self._warn_threshold)

    def _observe_metrics(self, seconds: float) -> None:
        for reg in _registries(self._registry):
            reg.counter(
                "bigdl_tpu_jit_compiles_total",
                "jax.jit compiles per tracked executable "
                "(one per new abstract shape signature).",
                labelnames=("fn",)).labels(self.name).inc()
            reg.histogram(
                "bigdl_tpu_jit_compile_seconds",
                "First-call wall time per new signature "
                "(trace + lower + compile or cache load + first "
                "dispatch; by stage: bigdl_tpu_jit_stage_seconds_total).",
                labelnames=("fn",)).labels(self.name).observe(seconds)


def tracked_jit(name: str, fn=None, *, registry=None,
                warn_threshold: Optional[int] = None, **jit_kwargs):
    """jax.jit with compile telemetry (see module docstring).

    ``tracked_jit("decode", fn, donate_argnums=(2,))`` or as a
    decorator factory: ``@tracked_jit("decode", donate_argnums=(2,))``.
    ``registry`` additionally mirrors the compile metrics into a
    non-default registry (e.g. the engine's)."""
    if fn is None:
        def deco(f):
            return TrackedJit(name, f, registry=registry,
                              warn_threshold=warn_threshold, **jit_kwargs)
        return deco
    return TrackedJit(name, fn, registry=registry,
                      warn_threshold=warn_threshold, **jit_kwargs)


def compile_table() -> Dict[str, Dict[str, Any]]:
    """JSON-ready snapshot of the process-wide compile table:
    {name: {compiles, total_s, peak_temp_bytes, signatures[...],
    last_compile_ts, storm}}. Signature rows carry "stages" (the first
    call's seconds by stage; "seconds" and ``total_s`` stay its wall to
    the call's return) and a "memory" dict (temp/argument/output/alias/
    generated-code bytes) when capture was on and the AOT analysis
    succeeded."""
    with _lock:
        out: Dict[str, Dict[str, Any]] = {}
        for name, ent in sorted(_table.items()):
            out[name] = {
                "compiles": ent["compiles"],
                "total_s": round(ent["total_s"], 6),
                "last_compile_ts": round(ent["last_compile_ts"], 6),
                "storm": ent["storm"],
                "peak_temp_bytes": ent.get("peak_temp_bytes", 0),
                "signatures": [
                    {k: (dict(v) if isinstance(v, dict) else v)
                     for k, v in s.items()} for s in ent["signatures"]],
            }
            for key in ("analytical_flops", "analytical_hbm_bytes"):
                if key in ent:
                    out[name][key] = ent[key]
        return out


def annotate_costs(name: str, flops: Optional[float] = None,
                   hbm_bytes: Optional[float] = None) -> None:
    """Attach analytical roofline costs (observability/roofline.py
    ``jit_costs``) to a tracked_jit's table entry, so the compile table
    carries bytes-moved/FLOPs next to compile counts. Creates the entry
    when the jit has not compiled yet (costs are known at engine build,
    compiles happen lazily)."""
    with _lock:
        ent = _table.setdefault(name, {
            "compiles": 0, "total_s": 0.0, "signatures": [],
            "last_compile_ts": 0.0, "storm": False,
            "peak_temp_bytes": 0})
        if flops is not None:
            ent["analytical_flops"] = float(flops)
        if hbm_bytes is not None:
            ent["analytical_hbm_bytes"] = float(hbm_bytes)


def top_offenders(limit: int = 8) -> list:
    """Tracked jits ranked by analytical HBM bytes moved (descending) —
    the roofline view of "which executable is the bandwidth bill".
    Entries without cost annotation rank last (by compile time)."""
    table = compile_table()
    rows = []
    for name, ent in table.items():
        rows.append({
            "name": name,
            "analytical_hbm_bytes": ent.get("analytical_hbm_bytes", 0.0),
            "analytical_flops": ent.get("analytical_flops", 0.0),
            "compiles": ent["compiles"],
            "total_s": ent["total_s"],
        })
    rows.sort(key=lambda r: (-r["analytical_hbm_bytes"], -r["total_s"]))
    return rows[:max(0, int(limit))]


def reset_compile_table() -> None:
    """Drop the process-wide table (tests / fresh bench runs). Does NOT
    reset per-wrapper signature sets — already-compiled executables stay
    uncounted, which is the truthful reading."""
    with _lock:
        _table.clear()
