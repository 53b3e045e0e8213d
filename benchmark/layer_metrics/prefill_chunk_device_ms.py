"""Per-layer metric ``prefill_chunk_device_ms``: device time of one
prefill chunk.

Device seconds inside the prefill programs of the traced stretch (trace
group ``prefill_programs``, the XLA Modules line) over the number of
times they ran in it, x 1000. A chunk is one run of an ``engine_prefill*``
program (``EngineConfig.prefill_chunk`` rows: 256 in the dense cells, 1024
in the sparse-latent cell), so this is the part of a chunk-carrying step
that the chunk itself holds the chip for; the step's p95 gap is that plus
the decode program and the host. A stretch without a prefill program reads
nothing: the DeepSeek-V2 cell's stretch often holds no chunk, so it does
not list this metric. The bursty cell lists it since PR 38 moved its
stretch into a burst (``traffic/chat-bursty.json``, ``assumed.trace``).
"""

LAYER = "model step"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(obs):
    tr = obs.get("trace")
    if not tr:
        return None
    g = tr["programs"].get("prefill_programs")
    if not g or not g["calls"] or not g["seconds"]:
        return None
    return 1000.0 * g["seconds"] / g["calls"]
