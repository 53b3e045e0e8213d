"""CLI: one-shot checkpoint converter.

Equivalent of the reference's `llm_convert` CLI (reference
convert_model.py:31-144: pth/HF -> ggml int4/int8 .bin, gptq -> ggml).
Here: HF dir or .gguf -> quantized save_low_bit directory, or -> GGUF
export (q4_0/q4_1/q5_0/q5_1/q8_0) for llama.cpp interop.
"""

from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="llm-convert-tpu",
        description="Convert a model to low-bit (llm_convert equivalent)")
    ap.add_argument("model", help="HF checkpoint dir or .gguf file")
    ap.add_argument("-o", "--outfile", required=True,
                    help="output directory (or .gguf path with -f gguf)")
    ap.add_argument("-t", "--outtype", default="sym_int4",
                    help="qtype: sym_int4/asym_int4/nf4/fp8_e4m3/... ")
    ap.add_argument("-f", "--format", default="lowbit",
                    choices=["lowbit", "gguf"])
    ap.add_argument("--imatrix", default=None,
                    help="llama.cpp-format importance matrix file for "
                         "weighted quantization (ultra-low-bit qtypes)")
    args = ap.parse_args(argv)

    from bigdl_tpu.config import enable_compilation_cache
    from bigdl_tpu.transformers.model import AutoModelForCausalLM

    enable_compilation_cache()
    model = AutoModelForCausalLM.from_pretrained(
        args.model, load_in_low_bit=args.outtype, imatrix=args.imatrix)

    if args.format == "lowbit":
        model.save_low_bit(args.outfile)
        print(f"saved low-bit checkpoint to {args.outfile}")
        return 0

    # GGUF export: dequantize leaves back to f32, re-encode as ggml blocks
    import numpy as np

    from bigdl_tpu import gguf as G
    from bigdl_tpu.ops.quant import QTensor, dequantize

    cfg = model.config
    # outtype was validated by from_pretrained above; qtypes without a
    # matching ggml block format (nf4, fp4, iq*, ...) re-encode at the
    # nearest width: 8-bit kinds as q8_0, everything else as q4_0
    exact = {
        "fp32": G.GGML_F32, "f32": G.GGML_F32,
        "fp16": G.GGML_F16, "f16": G.GGML_F16,
        "bf16": G.GGML_BF16,
        "sym_int4": G.GGML_Q4_0, "int4": G.GGML_Q4_0, "q4_0": G.GGML_Q4_0,
        "sym_int8": G.GGML_Q8_0, "int8": G.GGML_Q8_0, "q8_0": G.GGML_Q8_0,
        "fp8": G.GGML_Q8_0, "fp8_e4m3": G.GGML_Q8_0,
        "fp8_e5m2": G.GGML_Q8_0,
        "asym_int4": G.GGML_Q4_1, "q4_1": G.GGML_Q4_1,
        "sym_int5": G.GGML_Q5_0, "q5_0": G.GGML_Q5_0,
        "asym_int5": G.GGML_Q5_1, "q5_1": G.GGML_Q5_1,
    }
    gt = exact.get(args.outtype)
    if gt is None:
        gt = G.GGML_Q4_0
        print(f"warning: qtype '{args.outtype}' has no matching ggml "
              "block format; the GGUF will be re-encoded as q4_0 "
              "(different size and quantization than the in-memory "
              "model)", file=sys.stderr)

    def dense_oi(leaf, idx=None):
        """Leaf -> dense HF-orientation [out, in] f32."""
        if isinstance(leaf, QTensor):
            if idx is not None:
                import jax

                leaf = jax.tree.map(lambda x: x[idx], leaf)
            return np.asarray(dequantize(leaf), np.float32).T
        arr = np.asarray(leaf, np.float32)
        if idx is not None:
            arr = arr[idx]
        return arr.T

    from bigdl_tpu.models.llama import unmerge_projections

    # from_pretrained merges qkv/gate-up by default; GGUF tensor names
    # are per-projection, so restore the split layout (exact slicing)
    p = unmerge_projections(model.params, cfg)
    tensors = {"token_embd.weight":
               (np.asarray(p["embed_tokens"], np.float32), G.GGML_F16),
               "output_norm.weight":
               (np.asarray(p["norm"], np.float32), G.GGML_F32)}
    if "lm_head" in p:
        tensors["output.weight"] = (dense_oi(p["lm_head"]), gt)
    name_map = {"q_proj": "attn_q", "k_proj": "attn_k", "v_proj": "attn_v",
                "o_proj": "attn_output", "gate_proj": "ffn_gate",
                "up_proj": "ffn_up", "down_proj": "ffn_down"}
    for i in range(cfg.num_hidden_layers):
        for ours, theirs in name_map.items():
            if ours in p["layers"]:
                tensors[f"blk.{i}.{theirs}.weight"] = (
                    dense_oi(p["layers"][ours], i), gt)
        tensors[f"blk.{i}.attn_norm.weight"] = (
            np.asarray(p["layers"]["input_layernorm"][i], np.float32),
            G.GGML_F32)
        tensors[f"blk.{i}.ffn_norm.weight"] = (
            np.asarray(p["layers"]["post_attention_layernorm"][i],
                       np.float32), G.GGML_F32)

    kv = {
        "general.architecture": "llama",
        "llama.block_count": cfg.num_hidden_layers,
        "llama.embedding_length": cfg.hidden_size,
        "llama.feed_forward_length": cfg.intermediate_size,
        "llama.attention.head_count": cfg.num_attention_heads,
        "llama.attention.head_count_kv": cfg.num_key_value_heads,
        "llama.attention.layer_norm_rms_epsilon": cfg.rms_norm_eps,
        "llama.rope.freq_base": cfg.rope_theta,
        "llama.context_length": cfg.max_position_embeddings,
    }
    G.write_gguf(args.outfile, kv, tensors)
    print(f"wrote GGUF to {args.outfile}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
