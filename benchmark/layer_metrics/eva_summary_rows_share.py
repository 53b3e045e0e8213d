"""Per-layer metric ``eva_summary_rows_share``: of the rows a query of
chunked linearized attention reads in a decode step, the share that are
chunk summaries, over the window.

The program counts, once a decode step and from the positions the host
knows, by the kernel's own rule (``bigdl_tpu_eva_rows_total``):
``kind="window"`` the exact keys of the query's own window up to itself,
``kind="summary"`` one row a 16-position chunk of every earlier window,
``kind="context"`` the positions full attention would read. This is
summary / (window + summary); beside ``1 - (window + summary) /
context`` it says what the compression saves. Informational: it is a
property of where the traffic's positions sit in their windows, no
direction is better and no change to the program should move it
(``BENCHMARK.json`` has to give a direction and says ``higher``). A
program without the counter (the parent) reads nothing.
"""

from harness import promtext

LAYER = "model step"
SOURCE = "program_counter"
UNIT = "%"
MOVES = "itl_p95_ms"

ROWS = "bigdl_tpu_eva_rows_total"


def read(obs):
    s, e = obs.get("counters_start"), obs.get("counters_end")
    if e is None:
        return None
    summary = promtext.delta(s, e, ROWS, {"kind": "summary"})
    window = promtext.delta(s, e, ROWS, {"kind": "window"})
    if summary is None or window is None or not summary + window:
        return None
    return 100.0 * summary / (summary + window)
