"""Per-layer metric ``mla_chunk_attn_device_ms``: device time of the
latent chunk attention in one prefill chunk.

Device seconds of the trace group ``mla_chunk_attn`` (the
``mla_chunk_attention`` kernel inside prefill programs: one call a
sparse-latent layer, the chunk's rows against the live key blocks of the
layer's latent plane) over the number of prefill programs that ran in
the traced stretch, x 1000: the kernel's part of
``prefill_chunk_device_ms``. It grows with the keys already cached, so it
has no roofline here: the operations of the live blocks are a count the
benchmark does not make yet. A program without the kernel (a tree that
sweeps in XLA ops, a stretch without a chunk) reads nothing.
"""

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(obs):
    tr = obs.get("trace")
    if not tr:
        return None
    group = tr["groups"].get("mla_chunk_attn")
    programs = tr["programs"].get("prefill_programs")
    if not group or not group["seconds"] or not programs \
            or not programs["calls"]:
        return None
    return 1000.0 * group["seconds"] / programs["calls"]
