"""Per-layer metric ``setup_trace_s``: seconds the process spent tracing
programs before the window (``bigdl_tpu_jit_stage_seconds_total{stage=
"trace"}``, every ``fn``, exclusive of what nests inside a trace).

Read at the window's start, when set-up is over (``harness/
startup_account.py``). A program without the account reads nothing.
"""

from harness import startup_account

LAYER = "start-up"
SOURCE = "program_span"
UNIT = "s"
MOVES = "setup_s"


def read(obs):
    return startup_account.stage_seconds(obs, "trace")
