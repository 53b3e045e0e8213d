"""Per-layer metric ``setup_cache_hit_share``: of the TRACKED programs
handed to the backend compiler before the window, the share that the
persistent compile cache held.

``bigdl_tpu_compile_cache_requests_total{outcome="hit"}`` over both
outcomes, all ``fn`` less ``fn="untracked"`` (small eager programs
under JAX's caching thresholds miss in every run and would drown the
signal). It tells a run that found its programs (100 %) from one that
compiled some, on the ledger, for every pair: a ``setup_s`` that
differs between two such runs is the cache's, not the change's. Read at
the window's start, when set-up is over (``harness/
startup_account.py``). A program without the account reads nothing.
"""

from harness import startup_account

LAYER = "start-up"
SOURCE = "program_counter"
UNIT = "%"
MOVES = "setup_s"


def read(obs):
    hit = startup_account.tracked_cache_requests(obs, "hit")
    miss = startup_account.tracked_cache_requests(obs, "miss")
    if hit is None or miss is None or not hit + miss:
        return None
    return 100.0 * hit / (hit + miss)
