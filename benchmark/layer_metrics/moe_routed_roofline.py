"""Per-layer metric ``moe_routed_roofline``: the share of its roofline
that the routed-expert decode kernels reached in the traced stretch.

Device time of the trace group ``moe_routed`` (the ``moe_routed_decode``
kernels inside decode programs) against the least time for the bytes
they had to read over the chip's peak bandwidth. The bytes are those of
the experts HIT, never of all the experts held (a kernel that skips
idle experts would then read over 100 %): the window's mean of held
experts hit a layer-step (two program counters, as
``moe_experts_hit_share`` reads them) x the expert layers x the decode
programs' runs in the stretch x the packed bytes of one expert
(``costs_deepseek_v2.expert_bytes``, 13.27 MB at the published widths).
Bandwidth binds: at 32 rows a hit expert does 1.5 GFLOP against 13 MB.
A program without the kernels or the counters reads nothing.
"""

from harness import spec

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(obs):
    tr, peaks, work = obs.get("trace"), obs.get("peaks"), obs.get("work")
    if not tr or not peaks or not work:
        return None
    group = tr["groups"].get("moe_routed")
    programs = tr["programs"].get("decode_programs")
    if not group or not group["seconds"] or not programs:
        return None
    here = spec.import_file(
        "layer_metrics.moe_experts_hit_share",
        spec.Path(__file__).with_name("moe_experts_hit_share.py"))
    mean_hit = here.mean_hit(obs)
    if mean_hit is None:
        return None
    nbytes = (mean_hit * work["expert_layers"] * float(programs["calls"])
              * work["expert_bytes"])
    least = nbytes / (peaks["hbm_gbps"] * 1e9)
    return 100.0 * least / group["seconds"]
