"""Architecture registry: HF `architectures[0]` -> family adapter.

The reference's conversion engine special-cases 30 model families via
monkey-patched forwards chosen in `_optimize_post` (reference
transformers/convert.py:785-1357). Here each family is an adapter bundling
config parsing, checkpoint conversion, and forward functions; families that
are llama-shaped (mistral, qwen2, ...) reuse the llama module with config
deltas instead of carrying 400-line forks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional


@dataclasses.dataclass(frozen=True)
class FamilyAdapter:
    name: str
    config_from_hf: Callable[[Dict[str, Any]], Any]
    convert_params: Callable[..., Any]     # (tensors, cfg, qtype, ...) -> pytree
    forward: Callable                       # (params, cfg, tokens, cache)
    prefill: Callable                       # last-token-only variant
    forward_train: Optional[Callable]
    new_cache: Callable                     # (cfg, batch, max_seq, quantized)
    # Recurrent families (RWKV/mamba-style): the "cache" is absorbed state,
    # not a KV cache. Gates (a) speculative decoding (no rollback) and
    # (b) prompt padding in the Generator (state cannot mask pads).
    is_recurrent: bool = False

    # What the serving engine asks of a family beyond the fields above is
    # declared by the MODULE that owns `forward` (models/llama.py:
    # SUPPORTS_SCALED_KV, SUPPORTS_PAGED_KV, forward_paged,
    # new_paged_cache), and every adapter that routes through that
    # forward has it. Without these a model loaded by from_pretrained
    # could be served with neither an int8/int4 nor a paged cache —
    # only hand-built test families carried the attributes.
    def _forward_module(self, name: str, default=None):
        import sys

        return getattr(sys.modules[self.forward.__module__], name, default)

    @property
    def rewindable(self) -> bool:
        """Whether the cache can hold pad tokens past a prompt and be
        rewound to an earlier position: what the Generator's padding,
        both kinds of offline speculation and the serving engine's
        `speculative_tokens` (a rejected draft's row is disowned) ask. Not a recurrent family (absorbed
        state), and not one whose forward module says `CACHE_REWINDABLE
        = False` (a cache that REDUCES positions as it grows: chunked
        linearized attention). The serving engine batches the latter
        like any KV family and refuses only `is_recurrent`."""
        return not self.is_recurrent and bool(
            self._forward_module("CACHE_REWINDABLE", True))

    @property
    def SUPPORTS_SCALED_KV(self) -> bool:  # noqa: N802 — module's name
        return bool(self._forward_module("SUPPORTS_SCALED_KV", False))

    @property
    def SUPPORTS_PAGED_KV(self) -> bool:  # noqa: N802
        return bool(self._forward_module("SUPPORTS_PAGED_KV", False))

    @property
    def forward_paged(self) -> Optional[Callable]:
        return self._forward_module("forward_paged")

    @property
    def new_paged_cache(self) -> Optional[Callable]:
        return self._forward_module("new_paged_cache")

    @property
    def speculative_depth(self) -> Optional[Callable]:
        """`cfg -> int`: tokens the family's own draft module (multi-token
        prediction) runs ahead, for a family that has one; with it the
        forward module has `forward_hidden` (the forward that also
        returns the hidden rows) and `mtp_forward` (the module over
        hidden rows and their next tokens). The serving engine's
        `speculative_tokens` asks for these."""
        return self._forward_module("speculative_depth")

    @property
    def forward_hidden(self) -> Optional[Callable]:
        return self._forward_module("forward_hidden")

    @property
    def mtp_forward(self) -> Optional[Callable]:
        return self._forward_module("mtp_forward")

    @property
    def block_spec(self) -> Optional[Callable]:
        """`cfg -> BlockSpec` (`models/sdar_moe.py`): a family that
        generates by diffusion over blocks. Its step is a BLOCK: a call
        of `forward` with `length` rows a slot is one pass over a block,
        MASK ids where nothing is committed, and the serving engine's
        step commits the tokens the pass's confidences choose; None for
        a family whose step is one next token."""
        return self._forward_module("block_spec")

    @property
    def cache_spec(self) -> Optional[Callable]:
        """`cfg -> ops.kvcache.CacheSpec`: what the family's layers keep
        per position, where that is not K and V of `num_key_value_heads
        x hd` (a latent plane); None for the default."""
        return self._forward_module("cache_spec")


_REGISTRY: Dict[str, Any] = {}


def register_family(arch_names, adapter) -> None:
    """adapter: a FamilyAdapter, or a callable dispatcher
    `(hf_config | None) -> FamilyAdapter` for arch names shared by
    structurally different versions (chatglm v1 vs v2/3)."""
    for a in arch_names:
        _REGISTRY[a] = adapter


def get_family(arch: str,
               hf_config: Optional[Dict[str, Any]] = None) -> FamilyAdapter:
    try:
        entry = _REGISTRY[arch]
    except KeyError:
        raise ValueError(
            f"unsupported architecture {arch!r}; supported: "
            f"{sorted(_REGISTRY)}") from None
    if isinstance(entry, FamilyAdapter):
        return entry
    return entry(hf_config)


def supported_architectures():
    return sorted(_REGISTRY)


def _register_builtin() -> None:
    from bigdl_tpu.models import llama as llama_mod

    def llama_adapter(config_tweak=None):
        def cfg_from_hf(hf):
            cfg = llama_mod.LlamaConfig.from_hf(hf)
            return config_tweak(cfg, hf) if config_tweak else cfg
        return FamilyAdapter(
            name="llama",
            config_from_hf=cfg_from_hf,
            convert_params=llama_mod.convert_hf_params,
            forward=llama_mod.forward,
            prefill=llama_mod.forward_last_token,
            forward_train=llama_mod.forward_train,
            new_cache=llama_mod.new_cache,
        )

    register_family(
        ["LlamaForCausalLM", "MistralForCausalLM", "CodeLlamaForCausalLM",
         # llama-shaped aliases (the reference also routes these through
         # its llama forwards, convert.py:785-1357)
         "AquilaForCausalLM", "InternLMForCausalLM", "YiForCausalLM",
         "DeciLMForCausalLM"],
        llama_adapter())

    def qwen2_tweak(cfg, hf):
        # HF Qwen2 has QKV bias but no attention_bias flag in config.json
        return dataclasses.replace(cfg, attention_bias=True)

    register_family(["Qwen2ForCausalLM"], llama_adapter(qwen2_tweak))

    from bigdl_tpu.models import families

    families.register_all()

    from bigdl_tpu.models import mixtral as mixtral_mod

    register_family(
        ["MixtralForCausalLM"],
        FamilyAdapter(
            name="mixtral",
            config_from_hf=mixtral_mod.MixtralConfig.from_hf,
            convert_params=mixtral_mod.convert_hf_params,
            forward=mixtral_mod.forward,
            prefill=mixtral_mod.forward_last_token,
            forward_train=mixtral_mod.forward_train,
            new_cache=mixtral_mod.new_cache,
        ))

    from bigdl_tpu.models import deepseek_v2 as deepseek_mod

    # latent attention in the slab, group-limited routed experts with
    # shared experts; no paged forward (SUPPORTS_PAGED_KV absent), the
    # latent cache is bf16 only
    register_family(
        ["DeepseekV2ForCausalLM"],
        FamilyAdapter(
            name="deepseek_v2",
            config_from_hf=deepseek_mod.DeepseekV2Config.from_hf,
            convert_params=deepseek_mod.convert_hf_params,
            forward=deepseek_mod.forward,
            prefill=deepseek_mod.forward_last_token,
            forward_train=None,
            new_cache=deepseek_mod.new_cache,
        ))

    from bigdl_tpu.models import dots3_note as dots3_mod

    # latent attention of two kinds (sparse full layers with an index-key
    # plane, window layers in a ring), head-wise gate, sigmoid
    # bias-corrected router; slab only, bf16 planes only
    register_family(
        ["Dots3NoteForCausalLM"],
        FamilyAdapter(
            name="dots3_note",
            config_from_hf=dots3_mod.Dots3NoteConfig.from_hf,
            convert_params=dots3_mod.convert_hf_params,
            forward=dots3_mod.forward,
            prefill=dots3_mod.forward_last_token,
            forward_train=None,
            new_cache=dots3_mod.new_cache,
        ))

    from bigdl_tpu.models import deepseek_v32 as v32_mod

    # latent attention with learned sparse attention in every layer,
    # noaux_tc routing over groups, and an MTP module the serving engine
    # drafts with (speculative_depth); slab only, bf16 planes only
    register_family(
        ["DeepseekV32ForCausalLM"],
        FamilyAdapter(
            name="deepseek_v32",
            config_from_hf=v32_mod.DeepseekV32Config.from_hf,
            convert_params=v32_mod.convert_hf_params,
            forward=v32_mod.forward,
            prefill=v32_mod.forward_last_token,
            forward_train=None,
            new_cache=v32_mod.new_cache,
        ))

    from bigdl_tpu.models import evabyte as evabyte_mod

    # chunked linearized attention: K/V planes of one window beside a
    # summary plane of one column a chunk; slab only, bf16 planes only,
    # eight prediction heads of which head 0 is sampled
    register_family(
        ["EvaByteForCausalLM"],
        FamilyAdapter(
            name="evabyte",
            config_from_hf=evabyte_mod.EvaByteConfig.from_hf,
            convert_params=evabyte_mod.convert_hf_params,
            forward=evabyte_mod.forward,
            prefill=evabyte_mod.forward_last_token,
            forward_train=None,
            new_cache=evabyte_mod.new_cache,
        ))

    from bigdl_tpu.models import mimo_v2 as mimo_mod

    # grouped-query attention of two kinds (full planes beside K/V
    # rings of the window layers, keys wider than values, a sink in the
    # window layers' softmax), sigmoid bias-corrected router with no
    # shared expert; slab only, bf16 planes only
    register_family(
        ["MiMoV2ForCausalLM", "MiMoV2FlashForCausalLM"],
        FamilyAdapter(
            name="mimo_v2",
            config_from_hf=mimo_mod.MimoV2Config.from_hf,
            convert_params=mimo_mod.convert_hf_params,
            forward=mimo_mod.forward,
            prefill=mimo_mod.forward_last_token,
            forward_train=None,
            new_cache=mimo_mod.new_cache,
        ))

    from bigdl_tpu.models import afmoe as afmoe_mod

    # the window-and-full planes and kernels of mimo_v2 with a per-head
    # QK norm, a sigmoid gate on the attention output, no rotary in the
    # full layers, two norms a sublayer and a shared expert beside the
    # routed ones; the periods after the leading layers run as one scan;
    # slab only, bf16 planes only
    register_family(
        ["AfmoeForCausalLM"],
        FamilyAdapter(
            name="afmoe",
            config_from_hf=afmoe_mod.AfmoeConfig.from_hf,
            convert_params=afmoe_mod.convert_hf_params,
            forward=afmoe_mod.forward,
            prefill=afmoe_mod.forward_last_token,
            forward_train=None,
            new_cache=afmoe_mod.new_cache,
        ))

    from bigdl_tpu.models import sdar_moe as sdar_mod

    # generation by diffusion over blocks: mimo_v2's full planes and
    # kernels under a block-causal mask with a per-head QK norm, every
    # layer routed (no shared expert), one scan over all the layers; the
    # engine's step is a block pass (`block_spec`); slab only, bf16
    # planes only. The catalog's row states no `architectures`: the one
    # name is ISSUE 53's (the benchmark's file says so under `assumed`)
    register_family(
        ["SdarMoeForCausalLM"],
        FamilyAdapter(
            name="sdar_moe",
            config_from_hf=sdar_mod.SdarMoeConfig.from_hf,
            convert_params=sdar_mod.convert_hf_params,
            forward=sdar_mod.forward,
            prefill=sdar_mod.forward_last_token,
            forward_train=None,
            new_cache=sdar_mod.new_cache,
        ))

    from bigdl_tpu.models import rwkv as rwkv_mod

    def rwkv_adapter(version: int) -> FamilyAdapter:
        return FamilyAdapter(
            name=f"rwkv{version}",
            config_from_hf=lambda hf: rwkv_mod.RwkvConfig.from_hf(
                hf, version),
            convert_params=rwkv_mod.convert_hf_params,
            forward=rwkv_mod.forward,
            prefill=rwkv_mod.forward_last_token,
            forward_train=rwkv_mod.forward_train,
            new_cache=rwkv_mod.new_cache,
            is_recurrent=True,
        )

    register_family(["RwkvForCausalLM"], rwkv_adapter(4))
    register_family(["Rwkv5ForCausalLM", "RwkvWorldForCausalLM"],
                    rwkv_adapter(5))

    from bigdl_tpu.models import yuan as yuan_mod

    register_family(["YuanForCausalLM"], FamilyAdapter(
        name="yuan",
        config_from_hf=yuan_mod.config_from_hf,
        convert_params=yuan_mod.convert_hf_params,
        forward=yuan_mod.forward,
        prefill=yuan_mod.forward_last_token,
        forward_train=yuan_mod.forward_train,
        new_cache=yuan_mod.new_cache,
        # the LFA conv history cannot mask pad tokens or rewind
        is_recurrent=True,
    ))


_register_builtin()
