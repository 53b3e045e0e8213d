"""Quantized checkpoint serialization: save_low_bit / load_low_bit.

The reference persists quantized state dicts with a
`bigdl_transformers_low_bit` marker in config.json plus a key manifest
(reference transformers/model.py:56-92, 465-685; optimize.py:41-56).
Equivalent here: one directory with

  low_bit_weights.safetensors — every array leaf of the parameter pytree,
      flattened to "path.to.leaf" keys (QTensor fields as <name>#data,
      #scale, #zero, #aux). bfloat16 is stored as a uint16 view (safetensors
      numpy has no bf16) and restored via the manifest dtype.
  low_bit_manifest.json — pytree structure: per-leaf dtype + per-QTensor
      static metadata (qtype, logical shape), config dict, family name,
      the low_bit marker, and framework version.

Loading rebuilds the exact pytree on device with zero re-quantization work,
the fast path matching the reference's `load_low_bit`.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import __version__
from bigdl_tpu.ops.quant import QTensor

_WEIGHTS = "low_bit_weights.safetensors"
_MANIFEST = "low_bit_manifest.json"
MARKER = "bigdl_tpu_low_bit"


def _walk(tree: Any, prefix, arrays, meta):
    if isinstance(tree, dict):
        for k, v in tree.items():
            _walk(v, prefix + (str(k),), arrays, meta)
    elif isinstance(tree, QTensor):
        key = ".".join(prefix)
        meta[key] = {"kind": "qtensor", "qtype": tree.qtype,
                     "shape": list(tree.shape)}
        for field in ("data", "scale", "zero", "aux"):
            val = getattr(tree, field)
            if val is not None:
                arrays[f"{key}#{field}"] = val
    elif tree is None:
        pass
    else:
        key = ".".join(prefix)
        meta[key] = {"kind": "array"}
        arrays[key] = tree


def _to_numpy(x) -> Tuple[np.ndarray, str]:
    """Return (storable ndarray, logical dtype string).

    device_get can hand back a NON-contiguous host array; safetensors
    serializes the raw buffer without honoring strides, so everything
    is made C-contiguous before the dtype reinterpret."""
    arr = np.ascontiguousarray(np.asarray(jax.device_get(x)))
    name = str(arr.dtype)
    if arr.dtype == jnp.bfloat16:
        return arr.view(np.uint16), "bfloat16"
    if arr.dtype in (jnp.float8_e5m2, jnp.float8_e4m3fn):
        return arr.view(np.uint8), name
    return arr, name


def _from_numpy(arr: np.ndarray, dtype: str) -> jax.Array:
    if dtype == "bfloat16":
        return jnp.asarray(arr.view(jnp.bfloat16))
    if dtype in ("float8_e5m2", "float8_e4m3fn"):
        return jnp.asarray(arr.view(jnp.dtype(dtype)))
    return jnp.asarray(arr)


def save_low_bit(
    params: Any,
    path: str,
    config: Optional[Dict[str, Any]] = None,
    family: Optional[str] = None,
    qtype: Optional[str] = None,
    extra: Optional[Dict[str, Any]] = None,
) -> None:
    """Persist a (possibly quantized) parameter pytree to `path`."""
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    arrays: Dict[str, Any] = {}
    meta: Dict[str, Any] = {}
    _walk(params, (), arrays, meta)

    store: Dict[str, np.ndarray] = {}
    dtypes: Dict[str, str] = {}
    for k, v in arrays.items():
        store[k], dtypes[k] = _to_numpy(v)
    save_file(store, os.path.join(path, _WEIGHTS))

    manifest = {
        "format_version": 1,
        "bigdl_tpu_version": __version__,
        MARKER: qtype or "unknown",
        "family": family,
        "config": config or {},
        "leaves": meta,
        "dtypes": dtypes,
        "extra": extra or {},
    }
    with open(os.path.join(path, _MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)


def is_low_bit_dir(path: str) -> bool:
    return os.path.exists(os.path.join(path, _MANIFEST))


def load_manifest(path: str) -> Dict[str, Any]:
    with open(os.path.join(path, _MANIFEST)) as f:
        return json.load(f)


def load_low_bit_checked(
    path: str,
    accept_archs: Tuple[str, ...],
    class_name: str,
    imatrix: Any = None,
    required_keys: Tuple[str, ...] = (),
) -> Tuple[Any, Dict[str, Any], Dict[str, Any], Optional[str]]:
    """Manifest-first low-bit load for the facade classes: validates the
    saved architecture (and head keys) BEFORE deserializing weights, and
    rejects quantization-time kwargs, so a wrong-family multi-GB
    checkpoint is refused without touching its tensors.

    Returns (params, manifest, hf_config, qtype)."""
    if imatrix is not None:
        raise ValueError(
            "imatrix applies at quantization time; this path is an "
            "already-quantized save_low_bit directory — re-convert from "
            "the original checkpoint with the imatrix")
    manifest = load_manifest(path)
    hf_config = manifest["config"]
    archs = tuple(hf_config.get("architectures") or ("?",))
    if accept_archs and archs[0] not in accept_archs:
        raise ValueError(
            f"low-bit checkpoint at {path} was saved from {archs[0]!r}; "
            f"{class_name} supports {accept_archs}")
    missing = [k for k in required_keys
               if not any(leaf == k or leaf.startswith(f"{k}.")
                          for leaf in manifest["leaves"])]
    if missing:
        raise ValueError(
            f"low-bit checkpoint at {path} has no {missing} — saved from "
            f"a different task head than {class_name}")
    params, manifest = load_low_bit(path)
    return params, manifest, hf_config, manifest.get(MARKER)


def load_low_bit(path: str) -> Tuple[Any, Dict[str, Any]]:
    """Load (params pytree, manifest) saved by save_low_bit."""
    from safetensors.numpy import load_file

    manifest = load_manifest(path)
    store = load_file(os.path.join(path, _WEIGHTS))
    dtypes = manifest["dtypes"]

    def get(key):
        return _from_numpy(store[key], dtypes[key])

    params: Dict[str, Any] = {}
    for key, info in manifest["leaves"].items():
        parts = key.split(".")
        node = params
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        leaf_name = parts[-1]
        if info["kind"] == "qtensor":
            node[leaf_name] = QTensor(
                data=get(f"{key}#data"),
                scale=get(f"{key}#scale"),
                zero=get(f"{key}#zero") if f"{key}#zero" in store else None,
                qtype=info["qtype"],
                shape=tuple(info["shape"]),
                aux=get(f"{key}#aux") if f"{key}#aux" in store else None,
            )
        else:
            node[leaf_name] = get(key)
    return params, manifest
