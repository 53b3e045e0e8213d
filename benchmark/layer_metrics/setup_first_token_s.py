"""Per-layer metric ``setup_first_token_s``: the process's age when the
engine emitted its first token (``bigdl_tpu_startup_mark_seconds{mark=
"first_token"}``): an operator's cold start, process start to a
replica that has answered.

Read at the window's start, when set-up is over (``harness/
startup_account.py``). A program without the account reads nothing.
"""

from harness import startup_account

LAYER = "start-up"
SOURCE = "program_span"
UNIT = "s"
MOVES = "setup_s"


def read(obs):
    return startup_account.mark_seconds(obs, "first_token")
