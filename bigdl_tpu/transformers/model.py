"""User-facing model API: the reference's Auto* façade, TPU-native.

Mirrors `ipex_llm.transformers.AutoModelForCausalLM.from_pretrained(
load_in_4bit=True / load_in_low_bit="nf4")` (reference transformers/
model.py:104-336), `save_low_bit`/`load_low_bit` (model.py:56, 465), and the
`generate()` entry point — except nothing is monkey-patched: from_pretrained
streams HF safetensors straight into a quantized JAX pytree (one tensor on
host at a time) and returns a `TpuCausalLM` owning compiled prefill/decode
executables.
"""

from __future__ import annotations

import os
import shutil
from typing import Any, Dict, Optional

import numpy as np

from bigdl_tpu.generation import GenerationConfig, GenerationStats, Generator
from bigdl_tpu.models.registry import FamilyAdapter, get_family
from bigdl_tpu.ops.quant import FLOAT_QTYPES, get_qtype
from bigdl_tpu.transformers import lowbit_io
from bigdl_tpu.utils.hf import iter_hf_tensors, load_hf_config

_TOKENIZER_FILES = (
    "tokenizer.json", "tokenizer.model", "tokenizer_config.json",
    "special_tokens_map.json", "vocab.json", "merges.txt",
    "generation_config.json",
)


def _resolve_hub_path(path: str, model_hub: str) -> str:
    """`model_hub="modelscope"` resolves a repo id through ModelScope's
    snapshot_download (reference model.py:139-150); "huggingface" (the
    default) passes the path through — HF repo ids resolve inside
    utils/hf.py. Local paths bypass the hub either way."""
    if model_hub not in ("huggingface", "modelscope"):
        raise ValueError(
            "model_hub must be 'huggingface' or 'modelscope', got "
            f"{model_hub!r}")
    if model_hub == "modelscope" and not os.path.exists(path):
        try:
            from modelscope.hub.snapshot_download import snapshot_download
        except ImportError as e:
            raise ImportError(
                "model_hub='modelscope' needs the `modelscope` package "
                "(pip install modelscope), or pass a local path") from e
        return snapshot_download(path)
    return path


def _prepack(params: Any):
    """Load-time weight prepacking (ops/quant.prepack_tree): retile
    QTensor planes into the decode kernels' layout once, at load. The
    decode GEMV then loads int4 natively instead of burning the VPU on
    nibble unpacking (see ops/pallas/dequant_matmul._gemv_kernel_mxu).
    save_low_bit repacks to canonical. Returns (params, report)."""
    from bigdl_tpu.ops.quant import prepack_tree

    return prepack_tree(params)


def _maybe_merge(params: Any, cfg: Any, family: FamilyAdapter,
                 enable: bool) -> Any:
    """Apply merged-QKV / merged-gate-up weight surgery (the reference's
    `_optimize_pre`, transformers/convert.py:529-640) for generalized-
    decoder families. Exact (block quant is per-column); families with
    custom forwards (rwkv/chatglm-v1/yuan/encoder-decoders) keep their
    own layouts. Load with merge_projections=False for the split layout
    (adapter training targets / explicit-TP sharding need it)."""
    from bigdl_tpu.models import llama as llama_mod

    if family.forward is not llama_mod.forward:
        return params
    if not enable:
        # a low-bit dir saved from a default (merged) load carries the
        # merged layout — merge_projections=False must UNDO it, not just
        # skip merging, or the split-layout consumers (attach_lora,
        # shard_params_tp) dead-end on their own advice
        return llama_mod.unmerge_projections(params, cfg)
    return llama_mod.merge_projections(params, cfg)


class TpuCausalLM:
    """A loaded (possibly quantized) causal LM + compiled generation."""

    def __init__(
        self,
        params: Any,
        cfg: Any,
        family: FamilyAdapter,
        hf_config: Dict[str, Any],
        qtype: Optional[str],
        model_path: Optional[str] = None,
        max_seq: int = 2048,
        kv_quantized: bool = False,
        kv_cache_dtype: Optional[str] = None,
    ):
        from bigdl_tpu.ops.kvcache import resolve_kv_cache_dtype

        self.params, self.prepack_report = _prepack(params)
        self.config = cfg
        self.family = family
        self.hf_config = hf_config
        self.qtype = qtype
        self.model_path = model_path
        self.max_seq = max_seq
        self.kv_cache_dtype = resolve_kv_cache_dtype(
            kv_cache_dtype if kv_cache_dtype is not None else kv_quantized)
        self.kv_quantized = self.kv_cache_dtype != "bf16"
        self.draft_params: Any = None   # set when loaded with speculative=True
        # load-time quantization-error attribution
        # (observability/quality.py AttributionReport): populated by
        # from_pretrained when conversion ran under an attribution
        # collector; None for float loads, load_low_bit (no pre-quant
        # reference weights exist), and GGUF passthrough
        self.quality_report: Any = None
        self._generator: Optional[Generator] = None
        # packed weight bytes into the process memory ledger at build
        # time (postmortems / GET /v1/memory / bench reports read it);
        # best-effort — accounting never gates a load
        try:
            from bigdl_tpu.observability.memory import (default_ledger,
                                                        tree_nbytes)

            default_ledger().register(
                "weights", "causal_lm", tree_nbytes(self.params),
                qtype=qtype, family=getattr(family, "name",
                                            type(family).__name__))
            if self.prepack_report.get("qtensors"):
                default_ledger().register(
                    "weights", "prepack",
                    self.prepack_report.get("bytes_packed", 0),
                    **{k: v for k, v in self.prepack_report.items()
                       if k != "bytes_packed"})
        except Exception:
            pass

    # -- generation ---------------------------------------------------------
    @property
    def generator(self) -> Generator:
        if self._generator is None:
            self._generator = Generator(
                self.params, self.config,
                forward_fn=self.family.forward,
                prefill_fn=self.family.prefill,
                max_seq=self.max_seq,
                kv_cache_dtype=self.kv_cache_dtype,
                new_cache_fn=self.family.new_cache,
                recurrent=not self.family.rewindable,
            )
        return self._generator

    def generate(
        self,
        input_ids,
        max_new_tokens: int = 32,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        stats: Optional[GenerationStats] = None,
        gamma: int = 4,
        th_stop_draft: float = 0.8,
        auto_th_stop_draft: bool = True,
        prompt_lookup: bool = False,
        ngram: int = 2,
        spec_stats=None,
        visual=None,     # (vidx [B,S], vemb [Nv,D]) — multimodal prefill
        num_beams: int = 1,
        length_penalty: float = 1.0,
        **_ignored,
    ) -> np.ndarray:
        """HF-style generate: returns [B, prompt+new] (prompt included).

        When the model was loaded with speculative=True, decoding runs
        draft/verify speculation (bigdl_tpu.speculative) transparently —
        the reference patches GenerationMixin.generate the same way
        (speculative.py:42-103)."""
        ids = np.asarray(input_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        if eos_token_id is None:
            eos_token_id = self.hf_config.get("eos_token_id")
            if isinstance(eos_token_id, list):
                eos_token_id = eos_token_id[0]
        # prompt-lookup speculation: n-gram drafts from the context, no
        # draft model, exact greedy output (beyond the reference)
        if (prompt_lookup and ids.shape[0] == 1 and visual is None
                and num_beams <= 1 and not do_sample
                and self.family.rewindable):
            from bigdl_tpu.speculative import prompt_lookup_generate

            new = prompt_lookup_generate(
                self.params, self.config, ids,
                family_forward=self.family.forward,
                family_prefill=self.family.prefill,
                new_cache=self.family.new_cache,
                max_new_tokens=max_new_tokens,
                gamma=gamma,
                ngram=ngram,
                eos_token_id=eos_token_id,
                max_seq=self.max_seq,
                kv_cache_dtype=self.kv_cache_dtype,
                stats=spec_stats,
            )
            return np.concatenate([ids, new], axis=1)
        # beam search preempts speculation: beams change WHICH sequence
        # is returned (semantics), speculation only changes latency
        if (self.draft_params is not None and ids.shape[0] == 1
                and visual is None and num_beams <= 1):
            from bigdl_tpu.speculative import speculative_generate

            new = speculative_generate(
                self.params, self.draft_params, self.config, self.config,
                ids,
                family_forward=self.family.forward,
                family_prefill=self.family.prefill,
                new_cache=self.family.new_cache,
                max_new_tokens=max_new_tokens,
                gamma=gamma,
                do_sample=do_sample,
                temperature=temperature,
                top_k=top_k,
                top_p=top_p,
                eos_token_id=eos_token_id,
                max_seq=self.max_seq,
                seed=seed,
                kv_cache_dtype=self.kv_cache_dtype,
                th_stop_draft=th_stop_draft,
                auto_th_stop_draft=auto_th_stop_draft,
                stats=spec_stats,
            )
            return np.concatenate([ids, new], axis=1)
        if num_beams > 1:
            if visual is not None or do_sample:
                raise NotImplementedError(
                    "num_beams > 1 is greedy beam search (no sampling, "
                    "no multimodal prefill yet)")
            from bigdl_tpu.generation import beam_search

            new = beam_search(
                self.params, self.config, self.family.forward, ids,
                self.family.new_cache, num_beams=num_beams,
                max_new_tokens=max_new_tokens, max_seq=self.max_seq,
                length_penalty=length_penalty, eos_token_id=eos_token_id,
                prefill_fn=self.family.prefill)
            return np.concatenate([ids, new], axis=1)
        gen = GenerationConfig(
            max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, do_sample=do_sample,
            eos_token_id=eos_token_id, seed=seed)
        new = self.generator.generate(ids, gen, stats=stats, visual=visual)
        return np.concatenate([ids, new], axis=1)

    def generate_stream(
        self,
        input_ids,
        max_new_tokens: int = 32,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        **_ignored,
    ):
        """Streaming generate: yields ONE new token id (int, batch 1) per
        step — the TextIteratorStreamer-equivalent surface the langchain/
        llamaindex/FastChat integrations build their callbacks on."""
        ids = np.asarray(input_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        if ids.shape[0] != 1:
            raise ValueError("generate_stream is a batch-1 surface")
        if eos_token_id is None:
            eos_token_id = self.hf_config.get("eos_token_id")
            if isinstance(eos_token_id, list):
                eos_token_id = eos_token_id[0]
        gen = GenerationConfig(
            max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, do_sample=do_sample,
            eos_token_id=eos_token_id, seed=seed)
        for tok in self.generator.stream(ids, gen):
            t = int(tok[0])
            yield t
            if eos_token_id is not None and t == eos_token_id:
                return

    # -- persistence --------------------------------------------------------
    def save_low_bit(self, path: str) -> None:
        """Persist quantized weights + config (+tokenizer files if known).
        The canonical split-block packing is the interchange format —
        int4-dtype (MXU layout) weights repack before writing."""
        from bigdl_tpu.ops.quant import tree_from_mxu_layout

        lowbit_io.save_low_bit(
            tree_from_mxu_layout(self.params), path,
            config=self.hf_config,
            family=self.family.name,
            qtype=self.qtype,
            extra={"max_seq": self.max_seq},
        )
        if self.model_path and os.path.isdir(self.model_path):
            for fname in _TOKENIZER_FILES:
                src = os.path.join(self.model_path, fname)
                if os.path.exists(src):
                    shutil.copy(src, os.path.join(path, fname))


class TpuQwenVLCausalLM(TpuCausalLM):
    """Qwen-VL: the qwen1 text decoder + the ViT/resampler vision tower
    (models/qwen_vl.py; reference transformers/models/qwen_vl.py +
    convert.py:696-711). `generate(images=...)` accepts paths / PIL
    images / pixel arrays; with no `images`, in-band image paths in the
    token stream (the Qwen-VL tokenizer protocol) are decoded and loaded.
    """

    visual_cfg = None            # set by _attach_qwen_vl
    _encode_jit = None

    def encode_images(self, images) -> np.ndarray:
        """images -> [N, n_queries, hidden] visual features.

        A float [N, 3, S, S] array is taken as ALREADY CLIP-normalized
        pixels; uint8 / NHWC / list inputs go through preprocess_images
        (resize + /255 + CLIP mean/std)."""
        import functools

        import jax
        import jax.numpy as jnp

        from bigdl_tpu.models import qwen_vl as QV

        arr = np.asarray(images) if not isinstance(images, (list, tuple)) \
            else None
        if (arr is not None and arr.ndim == 4 and
                np.issubdtype(arr.dtype, np.floating)):
            if arr.shape[1] != 3:
                raise ValueError(
                    f"float pixel batches must be [N, 3, S, S] "
                    f"CLIP-normalized (got {arr.shape}); pass uint8 / "
                    "PIL / paths for automatic preprocessing")
            pixels = arr.astype(np.float32)
        elif arr is not None and arr.ndim == 4:
            pixels = QV.preprocess_images(list(arr), self.visual_cfg)
        else:
            pixels = QV.preprocess_images(images, self.visual_cfg)
        if self._encode_jit is None:
            from bigdl_tpu.observability.compile_watch import tracked_jit

            self._encode_jit = tracked_jit(
                "qwen_vl_encode_images", functools.partial(
                    QV.encode_images, vcfg=self.visual_cfg))
        return np.asarray(self._encode_jit(self.params["visual"],
                                           pixels=jnp.asarray(pixels)))

    def generate(self, input_ids, images=None, **kw) -> np.ndarray:
        from bigdl_tpu.models import qwen_vl as QV

        ids = np.asarray(input_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        vcfg = self.visual_cfg
        if images is not None and (isinstance(images, str)
                                   or not hasattr(images, "__len__")):
            images = [images]        # single path / PIL image
        if images is None and (ids == vcfg.image_start_id).any():
            images = QV.extract_image_paths(ids, vcfg)
            if any(p == "" for p in images):
                raise ValueError(
                    "prompt contains image spans with no in-band paths; "
                    "pass the images via generate(images=...)")
        if images is None or (hasattr(images, "__len__")
                              and len(images) == 0):
            return super().generate(ids, **kw)
        vidx, n_img = QV.visual_token_index(ids, vcfg)
        n_given = len(images) if hasattr(images, "__len__") else None
        if n_given is not None and n_given != n_img:
            raise ValueError(
                f"{n_img} image span(s) in the prompt but {n_given} "
                "image(s) supplied")
        feats = self.encode_images(images)
        if feats.shape[0] != n_img:
            raise ValueError(
                f"{n_img} image span(s) in the prompt but {feats.shape[0]} "
                "image(s) supplied")
        vemb = feats.reshape(-1, feats.shape[-1])
        return super().generate(ids, visual=(vidx, vemb), **kw)


def _attach_qwen_vl(model: TpuCausalLM) -> TpuCausalLM:
    """Upgrade a qwen1 TpuCausalLM to the VL facade when the checkpoint
    carries a vision tower (config['visual'] + params['visual'])."""
    if "visual" not in model.hf_config or "visual" not in model.params:
        return model
    from bigdl_tpu.models.qwen_vl import VisualConfig

    model.__class__ = TpuQwenVLCausalLM
    model.visual_cfg = VisualConfig.from_hf(model.hf_config["visual"])
    model._encode_jit = None
    return model


def _resolve_qtype(load_in_4bit: bool,
                   load_in_low_bit: Optional[str]) -> Optional[str]:
    if load_in_low_bit is not None:
        from bigdl_tpu.ops.quant import is_valid_qtype

        if (load_in_low_bit not in FLOAT_QTYPES
                and not is_valid_qtype(load_in_low_bit)):
            get_qtype(load_in_low_bit)  # raises with the known-qtype list
        return load_in_low_bit
    if load_in_4bit:
        return "sym_int4"
    return None


class _BaseAutoModelClass:
    """from_pretrained / load_low_bit, shared by the Auto* classes."""

    @classmethod
    def from_pretrained(
        cls,
        pretrained_model_name_or_path: str,
        *,
        load_in_4bit: bool = False,
        load_in_low_bit: Optional[str] = None,
        optimize_model: bool = True,   # accepted for API parity
        modules_to_not_convert=(),
        max_seq: Optional[int] = None,
        quantize_kv_cache: Optional[bool] = None,
        kv_cache_dtype: Optional[str] = None,
        speculative: bool = False,
        embedding_qtype: Optional[str] = None,
        imatrix: Optional[Any] = None,
        merge_projections: bool = True,
        model_hub: str = "huggingface",
        **_ignored,
    ) -> TpuCausalLM:
        from bigdl_tpu.config import default_kv_cache_dtype
        from bigdl_tpu.config import flags
        from bigdl_tpu.ops.kvcache import resolve_kv_cache_dtype

        if kv_cache_dtype is None:
            if quantize_kv_cache is None:
                # neither kwarg given: env/flag defaults decide
                kv_cache_dtype = default_kv_cache_dtype()
            else:
                kv_cache_dtype = resolve_kv_cache_dtype(quantize_kv_cache)
        else:
            kv_cache_dtype = resolve_kv_cache_dtype(kv_cache_dtype)
        path = _resolve_hub_path(pretrained_model_name_or_path, model_hub)
        if lowbit_io.is_low_bit_dir(path):
            if speculative:
                raise ValueError(
                    "speculative=True needs an original checkpoint to build "
                    "the low-bit draft (reference model.py:323-331); this "
                    "path is an already-quantized save_low_bit directory")
            if imatrix is not None:
                raise ValueError(
                    "imatrix applies at quantization time; this path is an "
                    "already-quantized save_low_bit directory — re-convert "
                    "from the original checkpoint with the imatrix")
            # max_seq=None lets the manifest's saved value win
            return cls.load_low_bit(path, max_seq=max_seq,
                                    kv_cache_dtype=kv_cache_dtype,
                                    merge_projections=merge_projections)
        if os.path.isfile(path) and path.endswith(".gguf"):
            if speculative:
                raise ValueError(
                    "speculative=True is not supported for GGUF inputs "
                    "(already low-bit); load the original HF checkpoint")
            if imatrix is not None:
                raise ValueError(
                    "imatrix applies at quantization time; GGUF weights "
                    "are already quantized — use the original HF "
                    "checkpoint with load_in_low_bit + imatrix")
            # direct GGUF ingestion (reference gguf/api.py:31)
            from bigdl_tpu.gguf import load_gguf

            params, hf_config, tok_info = load_gguf(path)
            archs = hf_config.get("architectures") or ["?"]
            family = get_family(archs[0], hf_config)
            cfg = family.config_from_hf(hf_config)
            params = _maybe_merge(params, cfg, family, merge_projections)
            model = TpuCausalLM(params, cfg, family, hf_config,
                                qtype="gguf",
                                model_path=os.path.dirname(path),
                                max_seq=max_seq or 2048,
                                kv_cache_dtype=kv_cache_dtype)
            # vocab already parsed once; CLIs reconstruct a tokenizer from
            # this instead of re-reading the file
            model.gguf_tokenizer_info = tok_info
            return model
        max_seq = max_seq or flags().default_max_seq

        qtype = _resolve_qtype(load_in_4bit, load_in_low_bit)
        hf_config = load_hf_config(path)
        archs = hf_config.get("architectures") or ["?"]
        family = get_family(archs[0], hf_config)
        cfg = family.config_from_hf(hf_config)

        tensor_stream = iter_hf_tensors(path)
        # GPTQ/AWQ checkpoints: repack already-quantized modules directly
        # (reference model.py:237-283 + convert.py:122-188 convert_gptq)
        from bigdl_tpu.transformers.gptq_awq import (detect_quant_config,
                                                     repack_stream)

        qc = detect_quant_config(hf_config)
        if qc is not None:
            if qtype not in (None, "sym_int4", "asym_int4"):
                raise ValueError(
                    f"checkpoint is already {qc[0]}-quantized (asym_int4 "
                    f"after repack); conflicting load_in_low_bit={qtype!r}")
            if imatrix is not None:
                raise ValueError(
                    f"imatrix applies at quantization time; this "
                    f"{qc[0]}-quantized checkpoint repacks as-is — use "
                    "the original float checkpoint with load_in_low_bit "
                    "+ imatrix")
            method, group, plus_one = qc
            tensor_stream = repack_stream(tensor_stream, method, group,
                                          plus_one)
            qtype = "asym_int4"   # remaining dense linears match the ckpt

        if isinstance(imatrix, str):
            # llama.cpp imatrix file, importance-weighted quantization
            # (reference imatrix= kwarg, model.py:104 + utils.py:187-323)
            from bigdl_tpu.imatrix import load_imatrix

            imatrix = load_imatrix(imatrix)

        cvt_qtype = None if (qtype in FLOAT_QTYPES) else qtype
        visual_tensors: list = []
        if "visual" in hf_config and archs[0] == "QWenLMHeadModel":
            # tee the vision tensors out of the one disk pass — the
            # decoder conversion skips them, and a second full read of a
            # multi-GB checkpoint just for the tower would double load IO
            def _tee(stream, sink):
                for name, w in stream:
                    if name.startswith("transformer.visual."):
                        sink.append((name, np.asarray(w)))
                    else:
                        yield name, w
            tensor_stream = _tee(tensor_stream, visual_tensors)
        # quantization-error attribution: run the conversion under a
        # collector so every Acc.linear records SNR/max-abs-err/clip
        # saturation vs the pre-quant floats (observability/quality.py).
        # config.quality_enabled() == False skips the collector and the
        # per-tensor dequant round-trip entirely.
        from bigdl_tpu.config import quality_enabled
        from bigdl_tpu.observability.quality import collect_attribution

        quality_report = None
        if quality_enabled() and cvt_qtype is not None:
            with collect_attribution() as quality_report:
                params = family.convert_params(
                    tensor_stream, cfg, qtype=cvt_qtype,
                    modules_to_not_convert=tuple(modules_to_not_convert),
                    imatrix=imatrix)
        else:
            params = family.convert_params(
                tensor_stream, cfg, qtype=cvt_qtype,
                modules_to_not_convert=tuple(modules_to_not_convert),
                imatrix=imatrix)
        if embedding_qtype is not None:
            # LowBitEmbedding equivalent (reference embedding.py:77-114,
            # embedding_qtype kwarg at model.py:104)
            from bigdl_tpu.ops.embedding import quantize_embedding

            params["embed_tokens"] = quantize_embedding(
                params["embed_tokens"], embedding_qtype)
        if "visual" in hf_config and archs[0] == "QWenLMHeadModel":
            # Qwen-VL: the vision tensors were tee'd out of the one
            # conversion stream (reference convert.py:696-711)
            from bigdl_tpu.models.qwen_vl import (VisualConfig,
                                                  convert_visual_params)

            params["visual"] = convert_visual_params(
                iter(visual_tensors),
                VisualConfig.from_hf(hf_config["visual"]))
        params = _maybe_merge(params, cfg, family, merge_projections)
        model = TpuCausalLM(params, cfg, family, hf_config, qtype,
                            model_path=path, max_seq=max_seq,
                            kv_cache_dtype=kv_cache_dtype)
        if quality_report is not None and len(quality_report):
            model.quality_report = quality_report
        model = _attach_qwen_vl(model)
        if speculative:
            # self-speculation: same checkpoint as a sym_int4 draft
            # (reference model.py:323-331)
            if not family.rewindable:
                raise ValueError(
                    f"speculative=True is not supported for the "
                    f"{family.name!r} family: verification rollback rewinds "
                    "a KV cache, and recurrent (RWKV-style) state or a "
                    "cache that reduces positions as it grows (one summary "
                    "a chunk beside one window of exact keys) cannot be "
                    "rewound; drafting several tokens a step from "
                    "prediction heads is not built")
            if cvt_qtype == "sym_int4":
                # already low-bit: share the (possibly MXU-relayouted)
                # tree — the draft decode is the latency-critical loop
                model.draft_params = model.params
            else:
                model.draft_params, _ = _prepack(_maybe_merge(
                    family.convert_params(
                        iter_hf_tensors(path), cfg, qtype="sym_int4",
                        modules_to_not_convert=tuple(
                            modules_to_not_convert)),
                    cfg, family, merge_projections))
        return model

    @classmethod
    def load_low_bit(cls, path: str, max_seq: Optional[int] = None,
                     quantize_kv_cache: bool = False,
                     kv_cache_dtype: Optional[str] = None,
                     merge_projections: bool = True,
                     **_ignored) -> TpuCausalLM:
        params, manifest = lowbit_io.load_low_bit(path)
        hf_config = manifest["config"]
        archs = hf_config.get("architectures") or ["?"]
        family = get_family(archs[0], hf_config)
        cfg = family.config_from_hf(hf_config)
        params = _maybe_merge(params, cfg, family, merge_projections)
        return _attach_qwen_vl(TpuCausalLM(
            params, cfg, family, hf_config,
            qtype=manifest.get(lowbit_io.MARKER),
            model_path=path,
            max_seq=max_seq or manifest.get("extra", {}).get("max_seq", 2048),
            kv_quantized=quantize_kv_cache,
            kv_cache_dtype=kv_cache_dtype,
        ))


class AutoModelForCausalLM(_BaseAutoModelClass):
    pass


class AutoModel(_BaseAutoModelClass):
    pass
