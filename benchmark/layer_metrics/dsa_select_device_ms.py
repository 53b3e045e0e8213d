"""Per-layer metric ``dsa_select_device_ms``: device time of the exact
top-k selection in one decode step.

Device seconds of the trace group ``dsa_select`` (the ``dsa_select``
kernel inside decode programs: one call a full layer, every slot's row
of index scores through two bisections in VMEM) over the number of
decode programs that ran in the traced stretch, x 1000. A selection has
no roofline: it moves 128 KB a slot and layer and is bound by the 45
dependent counts. A program without the kernel reads nothing.
"""

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(obs):
    tr = obs.get("trace")
    if not tr:
        return None
    group = tr["groups"].get("dsa_select")
    programs = tr["programs"].get("decode_programs")
    if not group or not group["seconds"] or not programs \
            or not programs["calls"]:
        return None
    return 1000.0 * group["seconds"] / programs["calls"]
