"""What the readers of the start-up account share (the per-layer
metrics of layer "start-up", which move ``setup_s``).

The program keeps the account itself (``bigdl_tpu/observability/
compile_watch.py``): every first call of a program by stage, and marks
on the process's own clock. These readers take the ABSOLUTE values at
``obs["counters_start"]``, the scrape at the window's start: set-up is
over then, and whatever a later compile adds belongs to the window's
own check (``no_compile_in_window``). A program without the account
(the parent of the PR that brought it) has no such series and every
reader returns None. Standard library only.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from harness import promtext

STAGE_SECONDS = "bigdl_tpu_jit_stage_seconds_total"
CACHE_REQUESTS = "bigdl_tpu_compile_cache_requests_total"
MARK_SECONDS = "bigdl_tpu_startup_mark_seconds"
# events of programs no tracked first call owns: small eager programs
# under JAX's caching thresholds miss in every run
UNTRACKED = {"fn": "untracked"}


def _total(obs: Dict[str, Any], series: str, labels: Dict[str, str]
           ) -> Optional[float]:
    snap = obs.get("counters_start")
    if snap is None:
        return None
    return promtext.total(snap, series, labels)


def stage_seconds(obs: Dict[str, Any], stage: str) -> Optional[float]:
    """Seconds all programs (``fn="untracked"`` too) spent in ``stage``
    before the window."""
    return _total(obs, STAGE_SECONDS, {"stage": stage})


def mark_seconds(obs: Dict[str, Any], mark: str) -> Optional[float]:
    """The process's age at ``mark``; None where it was not reached."""
    return _total(obs, MARK_SECONDS, {"mark": mark})


def tracked_cache_requests(obs: Dict[str, Any], outcome: str
                           ) -> Optional[float]:
    """Compile-cache requests of the TRACKED programs with ``outcome``
    before the window: all ``fn`` less ``fn="untracked"``."""
    every = _total(obs, CACHE_REQUESTS, {"outcome": outcome})
    if every is None:
        return None
    return every - (_total(obs, CACHE_REQUESTS,
                           dict(UNTRACKED, outcome=outcome)) or 0.0)
