"""Static-shape KV cache with low-bit storage dtypes.

TPU-native re-design of the reference's KV caching
(`DynamicNormalCache`/`DynamicFp8Cache`, reference transformers/kv.py:28-123,
and init/append/extend helpers in transformers/models/utils.py:38-153).

The reference grows its cache in 256-token blocks (realloc + copy) because
PyTorch tolerates dynamic shapes. Under XLA everything must be static: the
cache is **pre-allocated at max_seq_len** and appends are
`lax.dynamic_update_slice` writes at the current position — no realloc ever,
the jit-compiled decode step has one shape for its whole lifetime. Validity
is tracked by a scalar `pos`; attention masks keys at positions >= the
query's position + 1 (so garbage in the unwritten tail is never read).

Storage dtypes (`kv_cache_dtype`):

==========  =============================================================
bf16        plain bfloat16 (default)
fp8_e5m2    scale-free float8_e5m2, the reference's e5m2 cache
            (models/utils.py:99-153); upcast fused into the matmul read
int8        symmetric int8 codes + per-(token, head) f32 scales
int4        symmetric jnp.int4 codes (XLA packs two per byte) + scales
==========  =============================================================

int8/int4 quantize on append: each written [D] vector gets one absmax
scale, so appends at arbitrary (unaligned) positions never re-quantize
neighbours and slot reuse can never leak a stale scale. Scales live in
separate [L, B, S, Hkv] f32 planes (`k_scale`/`v_scale`, None for the
scale-free dtypes) so the code planes keep the exact cache layout the
attention kernels already stream.

A cache DESCRIBES ITS PLANES (`CacheSpec`, `KVCache.planes()`): K and V of
`[L, B, S, Hkv, hd]` with optional scale planes, or ONE latent plane of
`[L, B, latent_dim, S]` (multi-head latent attention: the normed compressed
KV and the shared roped key of a position, 576 values for DeepSeek-V2). The
latent plane keeps the positions in the LANES: 576 is 4.5 lane tiles, so a
`[.., S, 576]` plane would be padded to 640 on the chip, while 576 rows of S
positions tile without padding (576 = 36 x 16 sublanes of bf16). Every plane
is `[L, B, ...]`; `plane_seq_axis(name)` says where its positions run, and
the engine's splice, export and ledger code goes through that description
and names no plane.

A spec LISTS its planes (`PlaneSpec`: name, layers, what a position holds,
full length or ring), and allocation, splice, admission cost, the ledger
and the byte gauges follow the list. A model with two kinds of attention
layer keeps planes of different depths and lengths side by side
(`models/dots3_note.py`): `latent` `[Lf, B, 576, S]` and `index`
`[Lf, B, 128, S]` (the sparse-attention indexer's keys) for its full
layers, and `window` `[Lw, B, 1088, ring]` for its window layers, a RING:
position `p` lives in column `p % ring`, the ring is at least the window
long, and its length does not follow `max_seq`. A ring is whole or
nothing: it is spliced, exported and counted at its full length, and a
snapshot of it is the state at the length it was taken and at no shorter
one (`seeded` refuses it; the engine's host prefix cache refuses such a
spec).

A plane may also hold a REDUCTION of positions, one column every `stride`
of them (`models/evabyte.py`: `sum_k` / `sum_v` `[L, B, S / 16, H, hd]`,
the learned summary of each 16-position chunk), beside K/V planes of ONE
window (`win_k` / `win_v` `[L, B, 2048, H, hd]`, `PlaneSpec.window`):
position `p` lives in column `p % window`, the plane is refilled from
column 0 at every multiple of the window and only the columns `0 .. p %
window` are live, so a chunk's right padding inside the window lands on
columns nobody reads and a private prefill cache keeps the slab's
geometry (`unrolled` leaves such a plane alone). A cache of such planes
is the state at the length it was filled to and is valid as a prefix at
multiples of the window only: `seeded` and the host prefix cache refuse
it (`CacheSpec.has_strided`). `KVCache.stride` (static) is the stride of
its strided planes, which is how `max_seq` is read off them.

K/V planes of two kinds of layer (`models/mimo_v2.py`): the full
layers' `full_k` `[Lf, B, S, Hkv x hd_k]` and `full_v` `[Lf, B, S, Hkv x
hd_v]`, K and V of DIFFERENT widths, and the window layers' `ring_k` /
`ring_v` `[Lw, B, ring, Hkv' x hd]`, RINGS written at `pos % ring` whose
length does not follow `max_seq`. These four keep a position's heads
SIDE BY SIDE in the lanes (one row of `Hkv x hd` values, a multiple of
128): a `[.., Hkv, 192]` plane is padded to 256 lanes wherever a kernel
reads it (AOT for v5e: a copy of the whole K stack a call, PERF.md 6
PR 45), rows of 768 tile as they lie. They are rings or full planes
under the rules above: spliced, exported, counted and refused a
snapshot exactly as the latent ring is.

Layout: [num_layers, batch, max_seq, kv_heads, head_dim] — the whole stack is
one array per K/V so a `lax.scan` over layers can carry it. In place means
addressed on the stack: `update_layer` writes its rows at `[layer, ...]` and
decode attention reads block `(layer, b, s_block)` of the stack. Taking a layer
out by value (`dynamic_index_in_dim`) inside the scan is a copy of that layer's
whole slab on the chip, donated buffers or not: eight such copies per layer
were 41-46 % of the serving cells' device time (PERF.md, PR 26).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# canonical kv_cache_dtype names -> storage dtypes
KV_CACHE_DTYPES = {
    "bf16": jnp.bfloat16,
    "fp8_e5m2": jnp.float8_e5m2,
    "int8": jnp.int8,
    "int4": jnp.int4,
}
# dtypes that carry per-(token, head) scale planes
SCALED_KV_DTYPES = ("int8", "int4")
_KV_QMAX = {"int8": 127.0, "int4": 7.0}
_DTYPE_ALIASES = {"bfloat16": "bf16", "fp8": "fp8_e5m2",
                  "float8_e5m2": "fp8_e5m2", "e5m2": "fp8_e5m2"}

_warned_quantized_alias = False


def resolve_kv_cache_dtype(spec, default: str = "bf16") -> str:
    """Normalize a kv-cache dtype spec to a canonical name.

    Accepts the canonical strings (plus common aliases), None (-> default)
    and — for backward compatibility with the old `quantize_kv_cache` /
    `kv_quantized` booleans — True (deprecated alias for "fp8_e5m2",
    warned once per process) / False (-> default)."""
    global _warned_quantized_alias
    if spec is None:
        return default
    if isinstance(spec, bool):
        if spec:
            if not _warned_quantized_alias:
                _warned_quantized_alias = True
                warnings.warn(
                    "quantize_kv_cache/kv_quantized=True is deprecated; "
                    "use kv_cache_dtype='fp8_e5m2' (or 'int8'/'int4' for "
                    "block-scaled storage)", DeprecationWarning,
                    stacklevel=3)
            return "fp8_e5m2"
        return default
    s = str(spec).strip().lower()
    s = _DTYPE_ALIASES.get(s, s)
    if s not in KV_CACHE_DTYPES:
        raise ValueError(
            f"unknown kv_cache_dtype {spec!r}; choose from "
            f"{sorted(KV_CACHE_DTYPES)}")
    return s


def reject_scaled_kv(spec, family: str) -> None:
    """Guard for model families whose forward does not thread the
    int8/int4 scale planes: fail at cache allocation with a clear
    message instead of silently attending over raw codes."""
    if resolve_kv_cache_dtype(spec) in SCALED_KV_DTYPES:
        raise NotImplementedError(
            f"kv_cache_dtype int8/int4 is not supported by the "
            f"{family} family (its forward does not carry the scale "
            f"planes); use 'bf16' or 'fp8_e5m2'")


def kv_dtype_name(storage_dtype) -> str:
    """Canonical name for a cache storage dtype (inverse of the table)."""
    dt = jnp.dtype(storage_dtype)
    for name, d in KV_CACHE_DTYPES.items():
        if jnp.dtype(d) == dt:
            return name
    return str(dt)


# planes a cache may hold, in the order `planes()` lists them; every one
# is [L, B, ...] and its positions run along `plane_seq_axis(name)`
PLANE_NAMES = ("k", "v", "k_scale", "v_scale", "latent", "index", "window",
               "sum_k", "sum_v", "win_k", "win_v",
               "full_k", "full_v", "ring_k", "ring_v")
_SEQ_AXIS = {"latent": 3, "index": 3, "window": 3}
# planes that are rings (module docstring)
RING_PLANES = ("window", "ring_k", "ring_v")
# planes with one column every `stride` positions
STRIDED_PLANES = ("sum_k", "sum_v")
# K/V planes of one window, refilled from column 0
WINDOW_PLANES = ("win_k", "win_v")
# the one storage type a latent plane takes (a quantized latent reads
# noise at real widths: PERF.md 7, 17)
LATENT_KV_DTYPES = ("bf16",)


def plane_seq_axis(name: str) -> int:
    """Axis of plane `name` along which the positions run."""
    return _SEQ_AXIS.get(name, 2)


@dataclasses.dataclass(frozen=True)
class PlaneSpec:
    """One plane of a cache: the `KVCache` field it fills, the layers
    it stacks, what one position holds (`(kv_heads, head_dim)` of K or
    V, `(kv_heads,)` of a scale plane, `(width,)` of a plane that keeps
    its positions in the lanes) and, for a ring, how many positions it
    keeps (0: the cache's full length). `window`: the plane keeps one
    window of that many positions, refilled from column 0 (module
    docstring); `stride`: one column every so many positions."""
    name: str
    layers: int
    dims: Tuple[int, ...]
    ring: int = 0
    window: int = 0
    stride: int = 1

    def shape(self, batch: int, max_seq: int) -> Tuple[int, ...]:
        n = self.ring or self.window or -(-max_seq // self.stride)
        if plane_seq_axis(self.name) == 3:
            return (self.layers, batch) + self.dims + (n,)
        return (self.layers, batch, n) + self.dims


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What one family's layers keep per position: the declaration a
    family hands the cache manager (`cache_spec(cfg)` of the module that
    owns its forward; a family without one keeps K and V of
    `num_key_value_heads x hd`). kind "kv": K and V planes of
    `[L, B, S, kv_heads, head_dim]`, or the planes `planes` lists (bf16
    only: window and summary planes, or K/V planes of layers of two
    kinds, full-length and rings, K and V of their own widths); kind
    "latent": one plane of `[L, B, latent_dim, S]`, or the planes
    `planes` lists (bf16, as a latent plane is)."""
    kind: str
    num_layers: int
    kv_heads: int = 0
    head_dim: int = 0
    latent_dim: int = 0
    # int32 counters a family's forward accumulates on the device
    # (KVCache.stats); 0 = none
    stats_len: int = 0
    # a family with planes of several depths or lengths lists them
    planes: Tuple[PlaneSpec, ...] = ()

    def plane_specs(self, kv_cache_dtype=None) -> Tuple[PlaneSpec, ...]:
        """The planes a cache of this spec and storage type holds, in
        `PLANE_NAMES` order."""
        if self.kind == "latent":
            reject_non_bf16_latent(kv_cache_dtype)
            return self.planes or (
                PlaneSpec("latent", self.num_layers, (self.latent_dim,)),)
        if self.planes:
            reject_non_bf16_strided(kv_cache_dtype)
            return self.planes
        kv = (self.kv_heads, self.head_dim)
        out = (PlaneSpec("k", self.num_layers, kv),
               PlaneSpec("v", self.num_layers, kv))
        if resolve_kv_cache_dtype(kv_cache_dtype) in SCALED_KV_DTYPES:
            out += (PlaneSpec("k_scale", self.num_layers, kv[:1]),
                    PlaneSpec("v_scale", self.num_layers, kv[:1]))
        return out

    @property
    def has_ring(self) -> bool:
        return any(p.ring for p in self.planes)

    @property
    def stride(self) -> int:
        """Positions one column of the strided planes reduces (1: the
        spec has none)."""
        return max([p.stride for p in self.planes], default=1)

    @property
    def has_strided(self) -> bool:
        return self.stride > 1

    def unrolled(self) -> "CacheSpec":
        """This spec with every ring at the cache's full length, its
        positions in order: what a PRIVATE prefill cache and
        `generate()` hold. A prompt is right-padded to a chunk or a
        bucket, and the padding's rows, written past the prompt, would
        overwrite live columns of a ring (harmless garbage in a plane
        that keeps every position). `KVCache.spliced` rolls the last
        positions into the slab's ring. A spec without a ring is itself."""
        if not self.has_ring:
            return self
        return dataclasses.replace(self, planes=tuple(
            dataclasses.replace(p, ring=0) for p in self.planes))

    @property
    def seq_axis(self) -> int:
        """Axis of the positions in the first plane `planes()` lists (a
        host snapshot's `entry[0]`)."""
        return plane_seq_axis("latent" if self.kind == "latent" else "k")

    def values_per_position(self) -> int:
        """Cached values of one position of one layer (of the first
        plane's layers, where the planes differ)."""
        if self.kind == "latent":
            return self.latent_dim
        return 2 * self.kv_heads * self.head_dim


SNAPSHOT_REFUSAL = (
    "a cache with a strided plane (one column a chunk of positions, beside "
    "K/V planes of one window) cannot start from a prefix snapshot: it is "
    "the state at the length it was filled to and a valid prefix at "
    "multiples of the window only (prefix reuse at window boundaries is "
    "not built)")


def cache_spec_of(family, cfg) -> CacheSpec:
    """The `CacheSpec` of `family` for `cfg`."""
    fn = getattr(family, "cache_spec", None)
    if fn is not None:
        return fn(cfg)
    return CacheSpec("kv", cfg.num_hidden_layers,
                     cfg.num_key_value_heads, cfg.hd)


def reject_non_bf16_latent(spec) -> str:
    """A latent cache is bf16 only; says so instead of storing codes no
    kernel of this family reads."""
    name = resolve_kv_cache_dtype(spec)
    if name not in LATENT_KV_DTYPES:
        raise NotImplementedError(
            f"kv_cache_dtype {name!r} is not supported for a latent "
            f"(MLA) cache: it is stored in bf16 only (int4 and fp8 read "
            f"noise at real widths, PERF.md 7 (17); a quantized latent "
            f"cache is a later issue)")
    return name


def reject_non_bf16_strided(spec) -> str:
    """A K/V cache that lists its planes (window and summary planes,
    or full planes beside K/V rings) is bf16 only; says so instead of
    storing codes no kernel of the family reads."""
    name = resolve_kv_cache_dtype(spec)
    if name != "bf16":
        raise NotImplementedError(
            f"kv_cache_dtype {name!r} is not supported for a cache of "
            f"window and summary planes (chunked linearized attention) or "
            f"of full K/V planes beside K/V rings (window layers): "
            f"they are stored in bf16 only (a summary is a softmax-weighted "
            f"mean of 16 keys, and an fp8 or int8 plane fails the layer "
            f"check, PERF.md 6 PR 39; the ring kernel reads bf16 rows; a "
            f"quantized window, summary or ring plane is a later issue)")
    return name


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    k: Optional[jax.Array]    # [L, B, S_max, H_kv, D] storage dtype
    v: Optional[jax.Array]    # [L, B, S_max, H_kv, D]
    pos: jax.Array  # scalar int32: number of valid positions
    # per-(token, head) f32 dequant scales for int8/int4 storage;
    # None for the scale-free dtypes (bf16 / fp8_e5m2)
    k_scale: Optional[jax.Array] = None   # [L, B, S_max, H_kv] f32
    v_scale: Optional[jax.Array] = None
    # a latent (MLA) cache holds this one plane and no k / v
    latent: Optional[jax.Array] = None    # [L, B, latent_dim, S_max]
    # int32 counters the family's forward adds to on the device (sparse
    # experts: assignments, experts hit); read at scrape time
    stats: Optional[jax.Array] = None
    # sparse attention's index keys of the layers that keep `latent`
    index: Optional[jax.Array] = None     # [L, B, index_dim, S_max]
    # the window layers' latent rows, a ring (module docstring)
    window: Optional[jax.Array] = None    # [Lw, B, window_dim, ring]
    # chunked linearized attention: one learned summary a chunk of
    # `stride` positions, and the exact K/V of the current window
    sum_k: Optional[jax.Array] = None     # [L, B, S_max / stride, H, D]
    sum_v: Optional[jax.Array] = None
    win_k: Optional[jax.Array] = None     # [L, B, window, H, D]
    win_v: Optional[jax.Array] = None
    # K/V of layers of two kinds, a position's heads side by side in
    # the lanes: the full layers' planes and the window layers' rings
    full_k: Optional[jax.Array] = None    # [Lf, B, S_max, H_kv * D_k]
    full_v: Optional[jax.Array] = None    # [Lf, B, S_max, H_kv * D_v]
    ring_k: Optional[jax.Array] = None    # [Lw, B, ring, H_kv' * D_k]
    ring_v: Optional[jax.Array] = None
    # static: positions a column of the strided planes reduces
    stride: int = 1

    def tree_flatten(self):
        return (self.k, self.v, self.pos, self.k_scale, self.v_scale,
                self.latent, self.stats, self.index, self.window,
                self.sum_k, self.sum_v, self.win_k, self.win_v,
                self.full_k, self.full_v, self.ring_k,
                self.ring_v), self.stride

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, stride=aux or 1)

    def planes(self) -> Dict[str, jax.Array]:
        """The planes this cache holds, by name (`PLANE_NAMES` order)."""
        return {n: getattr(self, n) for n in PLANE_NAMES
                if getattr(self, n) is not None}

    def replace(self, **fields) -> "KVCache":
        """Same cache with some planes / `pos` / `stats` replaced."""
        return dataclasses.replace(self, **fields)

    @property
    def _first(self) -> Tuple[str, jax.Array]:
        return next(iter(self.planes().items()))

    @property
    def max_seq(self) -> int:
        name, plane = self._first
        n = plane.shape[plane_seq_axis(name)]
        return n * self.stride if name in STRIDED_PLANES else n

    @property
    def num_layers(self) -> int:
        return self._first[1].shape[0]

    @property
    def kv_dtype(self) -> str:
        """Canonical kv_cache_dtype name of the storage."""
        return kv_dtype_name(self._first[1].dtype)

    def reset_pos(self, pos) -> "KVCache":
        """Same buffers, new validity pointer (generation pad repair /
        speculative rollback)."""
        return self.replace(pos=pos)

    def seq_slices(self, length: int, row=None) -> Tuple[jax.Array, ...]:
        """Every plane cut to its first `length` positions (and to batch
        row `row`, kept as an axis of 1), in `planes()` order: what the
        prefix cache, export and migration move. A ring or a window
        plane is never cut: it comes whole, and is the state at this
        cache's own `pos`; a strided plane is cut to the columns of
        `length` positions."""
        out = []
        for name, p in self.planes().items():
            ax = plane_seq_axis(name)
            if name in STRIDED_PLANES:
                length_ = -(-length // self.stride)
            else:
                length_ = length
            if name not in RING_PLANES + WINDOW_PLANES:
                p = jax.lax.slice_in_dim(p, 0, min(length_, p.shape[ax]),
                                         axis=ax)
            if row is not None:
                p = jax.lax.slice_in_dim(p, row, row + 1, axis=1)
            out.append(p)
        return tuple(out)

    def seeded(self, host_planes, consumed: int) -> "KVCache":
        """This (empty, scalar-pos) cache with the first `consumed`
        positions of `host_planes` (numpy, `planes()` order) written in
        and `pos = consumed`: an admission that starts from a snapshot."""
        import numpy as np

        if any(n in STRIDED_PLANES for n in self.planes()):
            raise NotImplementedError(SNAPSHOT_REFUSAL)
        if any(n in RING_PLANES for n in self.planes()):
            raise NotImplementedError(
                "a cache with a ring plane cannot start from a prefix "
                "snapshot: the ring holds the last positions of the length "
                "it was taken at, not those of a shorter prefix")
        new = {}
        for (name, p), src in zip(self.planes().items(), host_planes):
            ax = plane_seq_axis(name)
            buf = np.zeros(p.shape, src.dtype)
            at = [slice(None)] * p.ndim
            at[ax] = slice(0, consumed)
            buf[tuple(at)] = src[tuple(at)]
            new[name] = jnp.asarray(buf)
        return self.replace(pos=jnp.asarray(consumed, jnp.int32), **new)

    def spliced(self, one: "KVCache", slot, plen) -> "KVCache":
        """This batched cache with the 1-row cache `one` written into
        batch row `slot` of every plane and `pos[slot] = plen`. `one` may
        be longer (chunk padding): it is cut to this cache's length. A
        ring of this cache takes the last positions before `plen` of
        `one`'s plane, which keeps its positions in order
        (`CacheSpec.unrolled`): column j gets the position `plen - 1 -
        ((plen - 1 - j) mod ring)` (nothing it will be read for where
        that is negative)."""
        new = {}
        ones = one.planes()
        for name, big in self.planes().items():
            ax = plane_seq_axis(name)
            if name in RING_PLANES and ones[name].shape[ax] != big.shape[ax]:
                ring = big.shape[ax]
                last = jnp.asarray(plen, jnp.int32) - 1
                at = last - jnp.mod(
                    last - jnp.arange(ring, dtype=jnp.int32), ring)
                src = jnp.take(
                    ones[name],
                    jnp.clip(at, 0, ones[name].shape[ax] - 1), axis=ax)
            else:
                src = jax.lax.slice_in_dim(
                    ones[name], 0, min(ones[name].shape[ax], big.shape[ax]),
                    axis=ax)
            at = [0] * big.ndim
            at[1] = slot
            new[name] = jax.lax.dynamic_update_slice(
                big, src.astype(big.dtype), tuple(at))
        if self.stats is not None and one.stats is not None:
            # what the prefill programs counted joins the slab's tally
            new["stats"] = self.stats + one.stats
        return self.replace(pos=self.pos.at[slot].set(plen), **new)


def init_cache(
    num_layers: int,
    batch: int,
    max_seq: int,
    kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    quantized=False,
    per_slot_pos: bool = False,
    kv_cache_dtype: Optional[str] = None,
) -> KVCache:
    """An empty K/V cache from its geometry: `init_cache_spec` of the
    "kv" spec. `quantized` is the deprecated boolean alias of
    `kv_cache_dtype` (True -> "fp8_e5m2") and also accepts a dtype name."""
    return init_cache_spec(
        CacheSpec("kv", num_layers, kv_heads, head_dim), batch, max_seq,
        kv_cache_dtype=resolve_kv_cache_dtype(
            kv_cache_dtype if kv_cache_dtype is not None else quantized),
        per_slot_pos=per_slot_pos, dtype=dtype)


def init_cache_spec(spec: CacheSpec, batch: int, max_seq: int,
                    kv_cache_dtype=None, per_slot_pos: bool = False,
                    dtype=jnp.bfloat16) -> KVCache:
    """Allocate the empty cache `spec` describes, plane by plane.

    `kv_cache_dtype` picks the storage of the code planes ("bf16" ->
    `dtype`, "fp8_e5m2", "int8", "int4"; scale planes are float32).
    per_slot_pos=True gives every batch row its own position counter —
    the continuous-batching layout (each serving slot decodes at its own
    depth, the capability the reference's vLLM port builds from per-seq
    KV dicts, vllm/model_executor/models/bigdl_model.py:88-139)."""
    name = resolve_kv_cache_dtype(kv_cache_dtype)
    dt = dtype if name == "bf16" else KV_CACHE_DTYPES[name]
    planes = {
        p.name: jnp.zeros(p.shape(batch, max_seq),
                          jnp.float32 if p.name.endswith("_scale") else dt)
        for p in spec.plane_specs(name)}
    if spec.has_strided:
        planes["stride"] = spec.stride
    return KVCache(
        k=planes.pop("k", None), v=planes.pop("v", None),
        pos=(jnp.zeros((batch,), jnp.int32) if per_slot_pos
             else jnp.zeros((), jnp.int32)),
        stats=(jnp.zeros((spec.stats_len,), jnp.int32)
               if spec.stats_len else None),
        **planes)


def update_latent(stack: jax.Array, layer, new: jax.Array,
                  pos: jax.Array) -> jax.Array:
    """Write `new` `[B, S_new, C]` into layer `layer` of the latent stack
    `[L, B, C, S_max]` at sequence offset `pos` (scalar, or `[B]` per
    slot): one `dynamic_update_slice`, or one scatter of the B x S_new
    new columns, on the stack itself, as `update_layer` does for K and V.
    A column past the end of the cache is dropped."""
    new = new.astype(stack.dtype)
    if getattr(pos, "ndim", 0) == 1:
        from bigdl_tpu.config import target_is_tpu

        b, s_new = new.shape[:2]
        if target_is_tpu():
            # the chip keeps the stack's positions in the lanes; XLA's
            # scatter of columns re-lays the whole stack out, twice
            # (a speculative verify step appends two rows a slot: the
            # append kernel once a row)
            if s_new <= 2 and stack.shape[-1] % 128 == 0:
                from bigdl_tpu.ops.pallas.mla_attention import (
                    latent_append_pallas)

                for r in range(s_new):
                    stack = latent_append_pallas(stack, layer, new[:, r],
                                                 pos + r)
                return stack

            def one(i, st):
                return jax.lax.dynamic_update_slice(
                    st, jnp.swapaxes(new[i], 0, 1)[None, None],
                    (layer, i, 0, pos[i]))

            return jax.lax.fori_loop(0, b, one, stack)
        slot = jnp.arange(b, dtype=jnp.int32)[:, None]
        at = pos[:, None] + jnp.arange(s_new, dtype=jnp.int32)[None, :]
        # the two index arrays sit either side of the slice, so the
        # updates are [B, S_new, C], as `new` is
        return stack.at[layer, slot, :, at].set(
            new, mode="drop", unique_indices=True)
    return jax.lax.dynamic_update_slice(
        stack, jnp.swapaxes(new, 1, 2)[None], (layer, 0, 0, pos))


def update_ring(stack: jax.Array, layer, new: jax.Array,
                pos: jax.Array) -> jax.Array:
    """Write `new` `[B, S_new, C]` into layer `layer` of the RING stack
    `[L, B, C, ring]`: row i of slot b lands in column `(pos[b] + i) %
    ring`. Of more rows than the ring keeps only the last `ring` are
    written (the earlier ones would be overwritten). One decoded row a
    slot goes through `update_latent` at `pos % ring` (its append kernel
    on the chip); a chunk is one scatter of its columns, on a stack that
    is a private prefill cache's and small."""
    ring = stack.shape[-1]
    b, s_new = new.shape[:2]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    if s_new == 1:
        return update_latent(stack, layer, new, posv % ring)
    keep = min(s_new, ring)
    at = (posv[:, None] + (s_new - keep)
          + jnp.arange(keep, dtype=jnp.int32)[None, :]) % ring
    slot = jnp.arange(b, dtype=jnp.int32)[:, None]
    return stack.at[layer, slot, :, at].set(
        new[:, s_new - keep:].astype(stack.dtype), unique_indices=True)


def update_rows(stack: jax.Array, layer, new: jax.Array, pos: jax.Array,
                ring: bool = False) -> jax.Array:
    """Write `new` `[B, S_new, W]` into layer `layer` of a stack `[L, B,
    S, W]` whose rows are positions: row i of slot b lands at `pos[b] +
    i` (`pos` a scalar or one a slot), or with `ring` in column `(pos[b]
    + i) % S`, where of more rows than the ring holds only the last `S`
    are written. One `dynamic_update_slice` for a scalar `pos` on a
    plane that keeps every position, else one scatter of the new rows;
    both address the stack itself, as `update_layer` does."""
    new = new.astype(stack.dtype)
    n = stack.shape[2]
    if getattr(pos, "ndim", 0) == 0 and not ring:
        return jax.lax.dynamic_update_slice(stack, new[None],
                                            (layer, 0, pos, 0))
    b, s_new = new.shape[:2]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    keep = min(s_new, n) if ring else s_new
    at = (jnp.maximum(posv, 0)[:, None] + (s_new - keep)
          + jnp.arange(keep, dtype=jnp.int32)[None, :])
    if ring:
        at = at % n
    slot = jnp.arange(b, dtype=jnp.int32)[:, None]
    return stack.at[layer, slot, at].set(
        new[:, s_new - keep:], indices_are_sorted=not ring,
        unique_indices=True, mode="drop")


def quantize_kv(x: jax.Array, storage_dtype) -> Tuple[jax.Array, jax.Array]:
    """Symmetric absmax quantization of the trailing [D] vectors.

    Returns (codes in storage_dtype, f32 scales of x.shape[:-1]).
    Zero vectors get scale 0 and all-zero codes (dequant is exact)."""
    qmax = _KV_QMAX[kv_dtype_name(storage_dtype)]
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / qmax
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    codes = jnp.clip(jnp.round(xf * inv[..., None]), -qmax, qmax)
    return codes.astype(storage_dtype), scale


def dequantize_kv(codes: jax.Array, scale: jax.Array,
                  compute_dtype=jnp.bfloat16) -> jax.Array:
    """codes [.., D] * scale [..] -> compute_dtype (dequant in f32)."""
    return (codes.astype(jnp.float32)
            * scale[..., None].astype(jnp.float32)).astype(compute_dtype)


def update_layer(
    cache_k: jax.Array,
    cache_v: jax.Array,
    layer: jax.Array | int,
    k_new: jax.Array,   # [B, S_new, H_kv, D]
    v_new: jax.Array,
    pos: jax.Array,     # scalar int32 write offset, or [B] per-slot offsets
    cache_ks: Optional[jax.Array] = None,   # [L, B, S_max, H_kv] f32
    cache_vs: Optional[jax.Array] = None,
):
    """Write k_new/v_new into layer `layer` at sequence offset `pos`.

    `pos` may be a vector of per-batch offsets (continuous-batching serving:
    every slot decodes at its own depth): one scatter of the B x S_new new
    rows per plane; a row past the end of the cache is dropped, and a slot
    at a negative offset (serving: it holds no request) writes at 0. A scalar
    `pos` is one `dynamic_update_slice` per plane. Both address the stack
    itself, so with donated inputs only the new rows move. Returns the
    updated full-stack arrays.

    With scale planes (`cache_ks`/`cache_vs`, int8/int4 storage) the new
    values are quantized on append — one absmax scale per written [D]
    vector, so unaligned offsets never disturb neighbouring tokens — and
    a 4-tuple (ck, cv, cks, cvs) is returned instead of (ck, cv).
    """
    scaled = cache_ks is not None
    if scaled:
        k_new, ks_new = quantize_kv(k_new, cache_k.dtype)
        v_new, vs_new = quantize_kv(v_new, cache_v.dtype)
    else:
        k_new = k_new.astype(cache_k.dtype)
        v_new = v_new.astype(cache_v.dtype)
    if getattr(pos, "ndim", 0) == 1:
        # row i of slot b lands at [layer, b, pos[b] + i]
        b, s_new = k_new.shape[:2]
        slot = jnp.arange(b, dtype=jnp.int32)[:, None]
        at = (jnp.maximum(pos, 0)[:, None]
              + jnp.arange(s_new, dtype=jnp.int32)[None, :])

        def put(stack, new):
            return stack.at[layer, slot, at].set(
                new, indices_are_sorted=True, unique_indices=True)

        ck, cv = put(cache_k, k_new), put(cache_v, v_new)
        if not scaled:
            return ck, cv
        return ck, cv, put(cache_ks, ks_new), put(cache_vs, vs_new)
    idx = (layer, 0, pos, 0, 0)
    ck = jax.lax.dynamic_update_slice(cache_k, k_new[None], idx)
    cv = jax.lax.dynamic_update_slice(cache_v, v_new[None], idx)
    if not scaled:
        return ck, cv
    sidx = (layer, 0, pos, 0)
    return (ck, cv,
            jax.lax.dynamic_update_slice(cache_ks, ks_new[None], sidx),
            jax.lax.dynamic_update_slice(cache_vs, vs_new[None], sidx))


def read_layer(
    cache_k: jax.Array,
    cache_v: jax.Array,
    layer: jax.Array | int,
    compute_dtype=jnp.bfloat16,
    cache_ks: Optional[jax.Array] = None,
    cache_vs: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full-length K/V for one layer, upcast (and dequantized when scale
    planes are given) from storage dtype, BY VALUE: a copy of the layer's
    slab. For callers that need the dense layer (GLM's own attention);
    cached attention goes through `sdp_attention(.., layer=)`, which hands
    the decode kernel the stack and slices only on the XLA path."""
    k = jax.lax.dynamic_index_in_dim(cache_k, layer, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache_v, layer, 0, keepdims=False)
    if cache_ks is not None:
        ks = jax.lax.dynamic_index_in_dim(cache_ks, layer, 0, keepdims=False)
        vs = jax.lax.dynamic_index_in_dim(cache_vs, layer, 0, keepdims=False)
        return (dequantize_kv(k, ks, compute_dtype),
                dequantize_kv(v, vs, compute_dtype))
    return k.astype(compute_dtype), v.astype(compute_dtype)


def _logical_nbytes(a: jax.Array) -> int:
    """Logical storage bytes: int4 packs two codes per byte (same
    convention as QTensor.nbytes in ops/quant.py)."""
    if jnp.dtype(a.dtype) == jnp.dtype(jnp.int4):
        return -(-a.size // 2)
    return a.size * jnp.dtype(a.dtype).itemsize


def kv_cache_nbytes(num_layers: int, batch: int, max_seq: int,
                    kv_heads: int, head_dim: int,
                    kv_cache_dtype: Optional[str] = None) -> Dict[str, int]:
    """`cache_nbytes` of a K/V cache from its geometry."""
    return cache_nbytes(CacheSpec("kv", num_layers, kv_heads, head_dim),
                        batch, max_seq, kv_cache_dtype)


def cache_nbytes(spec: CacheSpec, batch: int, max_seq: int,
                 kv_cache_dtype: Optional[str] = None) -> Dict[str, int]:
    """Storage footprint of a WOULD-BE cache, computed from the planes
    its spec lists without allocating anything — byte-for-byte identical
    to ``kv_cache_bytes(init_cache_spec(...))`` (the memory ledger and
    the engine's admission-cost estimate depend on that exactness; tests
    assert it). Components: code planes (K and V, or the latent, index
    and window planes; int4 at two codes per byte), scale planes, total."""
    name = resolve_kv_cache_dtype(kv_cache_dtype)
    item = jnp.dtype(KV_CACHE_DTYPES[name]).itemsize
    codes = scales = 0
    for p in spec.plane_specs(name):
        n = 1
        for d in p.shape(batch, max_seq):
            n *= d
        if p.name.endswith("_scale"):
            scales += n * jnp.dtype(jnp.float32).itemsize
        elif name == "int4":
            codes += -(-n // 2)
        else:
            codes += n * item
    return {"codes": codes, "scales": scales, "total": codes + scales}


def kv_cache_bytes(cache: KVCache) -> Dict[str, int]:
    """Storage footprint of a cache by its planes: codes (K and V, or the
    latent plane), scale planes, total."""
    planes = cache.planes()
    scales = sum(_logical_nbytes(p) for n, p in planes.items()
                 if n.endswith("_scale"))
    codes = sum(_logical_nbytes(p) for n, p in planes.items()
                if not n.endswith("_scale"))
    return {"codes": codes, "scales": scales, "total": codes + scales}


def publish_kv_cache_bytes(cache: KVCache, registry=None) -> Dict[str, int]:
    """Set the `bigdl_tpu_kv_cache_bytes` gauge (labelled by cache dtype
    and component) from a cache's storage footprint. Best-effort: metric
    export never gates cache allocation."""
    sizes = kv_cache_bytes(cache)
    try:
        if registry is None:
            from bigdl_tpu.observability import default_registry
            registry = default_registry()
        g = registry.gauge(
            "bigdl_tpu_kv_cache_bytes",
            "KV cache storage bytes by dtype and component "
            "(codes | scales | total, and latent | index | window | "
            "window_kv | summary | full_kv | ring_kv for such planes); "
            "int4 counted at two codes per byte",
            labelnames=("dtype", "component"))
        for comp, val in sizes.items():
            g.labels(cache.kv_dtype, comp).set(float(val))
        for comp, names in (("latent", ("latent",)), ("index", ("index",)),
                            ("window", ("window",)),
                            ("window_kv", ("win_k", "win_v")),
                            ("summary", ("sum_k", "sum_v")),
                            ("full_kv", ("full_k", "full_v")),
                            ("ring_kv", ("ring_k", "ring_v"))):
            held = [getattr(cache, n) for n in names
                    if getattr(cache, n) is not None]
            if held:
                g.labels(cache.kv_dtype, comp).set(
                    float(sum(_logical_nbytes(p) for p in held)))
    except Exception:
        pass
    return sizes
