"""Per-layer metric ``swa_rows_read_share``: of the rows of K that the
decode steps of a model of window and full layers read, the share read
in the window layers' rings, over the window.

The program counts, once a decode step and from the positions the host
knows, by the kernels' own rule (``bigdl_tpu_swa_rows_total``):
``kind="window"`` the last ``sliding_window`` positions of every live
slot in every window layer, ``kind="full"`` every position up to the
query's in every full layer, ``kind="context"`` what as many layers,
all full, would read. This is window / (window + full); beside ``1 -
(window + full) / context`` it says what the windows save.
Informational: it is a property of where the traffic's positions sit,
no direction is better and no change to the program should move it
(``BENCHMARK.json`` has to give a direction and says ``higher``). A
program without the counter (the parent) reads nothing.
"""

from harness import promtext

LAYER = "model step"
SOURCE = "program_counter"
UNIT = "%"
MOVES = "itl_p95_ms"

ROWS = "bigdl_tpu_swa_rows_total"


def read(obs):
    s, e = obs.get("counters_start"), obs.get("counters_end")
    if e is None:
        return None
    window = promtext.delta(s, e, ROWS, {"kind": "window"})
    full = promtext.delta(s, e, ROWS, {"kind": "full"})
    if window is None or full is None or not window + full:
        return None
    return 100.0 * window / (window + full)
