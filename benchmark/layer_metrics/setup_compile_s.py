"""Per-layer metric ``setup_compile_s``: seconds the backend compiled
before the window (``bigdl_tpu_jit_stage_seconds_total{stage="compile"}``,
every ``fn``): programs the persistent compile cache did not hold. A warm
run should read near 0 here; what is left is the small eager programs
under JAX's caching thresholds (``fn="untracked"``).

Read at the window's start, when set-up is over (``harness/
startup_account.py``). A program without the account reads nothing.
"""

from harness import startup_account

LAYER = "start-up"
SOURCE = "program_span"
UNIT = "s"
MOVES = "setup_s"


def read(obs):
    return startup_account.stage_seconds(obs, "compile")
