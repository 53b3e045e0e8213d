"""Per-layer metric ``setup_cache_load_s``: seconds spent finding programs in
the persistent compile cache and loading them, before the window
(``bigdl_tpu_jit_stage_seconds_total{stage="cache_load"}``, every
``fn``): the key's hashing, the read, the executable's load.

Read at the window's start, when set-up is over (``harness/
startup_account.py``). A program without the account reads nothing.
"""

from harness import startup_account

LAYER = "start-up"
SOURCE = "program_span"
UNIT = "s"
MOVES = "setup_s"


def read(obs):
    return startup_account.stage_seconds(obs, "cache_load")
