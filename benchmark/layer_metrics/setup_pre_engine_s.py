"""Per-layer metric ``setup_pre_engine_s``: the process's age when
``LLMEngine.__init__`` began (``bigdl_tpu_startup_mark_seconds{mark=
"engine_init_begin"}``): the interpreter, the imports, the backend and
the weights the host application built before it made an engine.

Read at the window's start, when set-up is over (``harness/
startup_account.py``). A program without the account reads nothing.
"""

from harness import startup_account

LAYER = "start-up"
SOURCE = "program_span"
UNIT = "s"
MOVES = "setup_s"


def read(obs):
    return startup_account.mark_seconds(obs, "engine_init_begin")
