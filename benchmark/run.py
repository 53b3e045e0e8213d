"""The benchmark's command: one cell, once, in a fresh process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights on the device from the seed, the program's engine and
server, warm-up of every shape), a measured window of ``--seconds``,
and as the LAST line of standard output one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``; last of all ``compared``: every number
``correct`` rests on beside its limit). ``--trace 0`` gives the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.

Without a TPU, with fewer chips than the cell asks for, or on a device
kind that ``harness/peaks.json`` lacks, it exits non-zero and prints no
result. ``--tiny`` (toy widths, CPU, interpret-mode kernels) is for
rehearsals and the tests: it reports ``platform: cpu`` and leaves out
every time, rate and share of the device.

A cell is files plus entries: ``configs/<config>.json``,
``traffic/<traffic>.json`` (its ``"runner"`` names
``harness/<runner>_runner.py``), one reader per per-layer metric under
``layer_metrics/``, and the entries in ``BENCHMARK.json``. A
configuration of another architecture also brings its own reference,
weights and costs modules, and one whose step is not one next token a
sequence its own generation module, which its file names under
``"harness"`` (``harness/__init__.py`` has the contract of each). The last lines of
standard error give every number ``correct`` compared beside its limit,
and then the run's wall time, process start to the result line, beside
the harness's budget (``common.RUN_BUDGET_S``): ``wall_s = <n> budget
<b>: ok|OVER``. The note line ``"info": "run"`` says where it went
(``phases``).
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()     # set-up counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT))

EXIT_NO_DEVICE = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--sweep", default=None,
                    help="builder's tool: comma-separated open-loop rates "
                         "to try, one window each, before the cell's own "
                         "window (finds the knee once; no check uses it)")
    ap.add_argument("--override", default=None,
                    help="builder's tool: a JSON object laid over the "
                         "configuration's file, for the control of "
                         "`correct` (a cache of lower precision); no "
                         "check uses it")
    ap.add_argument("--root", default=str(ROOT),
                    help="tree that holds BENCHMARK.json and benchmark/ "
                         "(the tests point it at a copy)")
    args = ap.parse_args(argv)

    from harness import common, spec

    try:
        cell = spec.Cell(args.workload, Path(args.root), tiny=args.tiny)
    except spec.SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if args.override:
        cell.config = spec.deep_update(cell.config,
                                       json.loads(args.override))
    seconds = (args.seconds if args.seconds is not None
               else float(cell.bench["run_seconds"]))

    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
    out_dir = Path(args.root) / "benchmark" / ".out" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)

    import jax

    if not args.tiny:
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not cache:
            jax.config.update(
                "jax_compilation_cache_dir",
                str(Path(args.root) / "benchmark" / ".cache" / "jax"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = None
    if not args.tiny:
        if device["platform"] != "tpu":
            print(f"benchmark: needs a TPU, found {device}; there is no "
                  "CPU fallback (--tiny is for rehearsals only)",
                  file=sys.stderr)
            return EXIT_NO_DEVICE
        if device["count"] < cell.chips:
            print(f"benchmark: cell {cell.name} needs {cell.chips} chips, "
                  f"found {device['count']}", file=sys.stderr)
            return EXIT_NO_DEVICE
        try:
            peaks = spec.peaks_for(device["kind"], Path(args.root))
        except spec.SpecError as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return EXIT_NO_DEVICE

    runner_name = cell.traffic.get("runner")
    try:
        runner = importlib.import_module(f"harness.{runner_name}_runner")
    except ImportError as e:
        print(f"benchmark: traffic {cell.traffic_name!r} names runner "
              f"{runner_name!r}: {e}", file=sys.stderr)
        return 2
    extra = {}
    if args.sweep:
        extra["sweep_rates"] = [float(x) for x in args.sweep.split(",")]
    try:
        result = runner.run(cell, seed=args.seed, seconds=seconds,
                            trace=bool(args.trace), tiny=args.tiny,
                            t_process=T_PROCESS, out_dir=out_dir,
                            device=device, peaks=peaks, **extra)
    except spec.SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if args.trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    # every number ``correct`` compared, beside its limit: the last key
    line["compared"] = result.get("compared", {})
    print(json.dumps(line), flush=True)
    common.report_wall(time.monotonic() - T_PROCESS)
    return 0


if __name__ == "__main__":
    sys.exit(main())
