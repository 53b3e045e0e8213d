"""Per-layer metric ``moe_experts_hit_share``: of the routed experts this
chip holds, the share that at least one token chose, a layer-step (a
routed layer of one decode step or prefill chunk), over the window.

Two counters the program accumulates on the device inside its decode
and prefill programs (``bigdl_tpu_moe_experts_hit_total`` over
``bigdl_tpu_moe_layer_steps_total``), divided by the experts held
(``work["held_experts"]``, from the configuration's shapes). It is what
the routed decode kernel's bytes go with: an expert nobody chose is not
read. A program without the counters (the parent) reads nothing.
"""

from harness import promtext

LAYER = "model step"
SOURCE = "program_counter"
UNIT = "%"
MOVES = "itl_p95_ms"

HIT = "bigdl_tpu_moe_experts_hit_total"
LAYER_STEPS = "bigdl_tpu_moe_layer_steps_total"


def mean_hit(obs):
    """Held experts hit a layer-step, the window's mean; None where the
    counters are not there."""
    s, e = obs.get("counters_start"), obs.get("counters_end")
    if e is None:
        return None
    hit = promtext.delta(s, e, HIT)
    steps = promtext.delta(s, e, LAYER_STEPS)
    if hit is None or not steps:
        return None
    return hit / steps


def read(obs):
    held = (obs.get("work") or {}).get("held_experts")
    mean = mean_hit(obs)
    if mean is None or not held:
        return None
    return 100.0 * mean / held
