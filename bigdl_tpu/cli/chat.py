"""CLI: one-shot generation and interactive chat.

Equivalent of the reference's `llm-cli` / `llm-chat` scripts (reference
cli/llm-cli:25-57 picks a per-family native binary; portable-zip/chat.py is
the interactive loop). Here one CLI drives every family through the
framework; `-x/--model-family` is accepted for command-line compatibility
but the architecture is auto-detected from the checkpoint.
"""

from __future__ import annotations

import argparse
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="bigdl-tpu-cli",
        description="Low-bit LLM generation on TPU (llm-cli equivalent)")
    ap.add_argument("-m", "--model", required=True,
                    help="HF checkpoint dir, save_low_bit dir, or .gguf")
    ap.add_argument("-x", "--model-family", default=None,
                    help="accepted for llm-cli compatibility (auto-detected)")
    ap.add_argument("-p", "--prompt", default=None,
                    help="one-shot prompt (omit for interactive chat)")
    ap.add_argument("-n", "--n-predict", type=int, default=128)
    ap.add_argument("--low-bit", default="sym_int4")
    ap.add_argument("-t", "--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--max-seq", type=int, default=2048)
    ap.add_argument("--speculative", action="store_true")
    ap.add_argument("--stats", action="store_true",
                    help="print first/next token latency after each turn")
    return ap


def _load(args):
    from bigdl_tpu.transformers.model import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        args.model, load_in_low_bit=args.low_bit, max_seq=args.max_seq,
        speculative=args.speculative)
    tokenizer = None
    try:
        from transformers import AutoTokenizer

        tokenizer = AutoTokenizer.from_pretrained(args.model)
    except Exception:
        tok_info = getattr(model, "gguf_tokenizer_info", None)
        if tok_info:
            # reconstruct from the GGUF vocabulary already parsed at load
            # (reference gguf/api.py)
            try:
                from bigdl_tpu.gguf_tokenizer import GGUFTokenizer

                tokenizer = GGUFTokenizer.from_tokenizer_info(tok_info)
                print("using tokenizer reconstructed from GGUF vocab",
                      file=sys.stderr)
            except ValueError as e:
                print(f"gguf tokenizer unusable ({e})", file=sys.stderr)
        if tokenizer is None:
            print("warning: no tokenizer found; token-id mode",
                  file=sys.stderr)
    return model, tokenizer


def _generate(model, tokenizer, text, args, history=None):
    from bigdl_tpu.generation import GenerationStats

    if tokenizer is None:
        ids = [int(x) for x in text.split()]
    elif history is not None and hasattr(tokenizer, "apply_chat_template"):
        history.append({"role": "user", "content": text})
        ids = tokenizer.apply_chat_template(history, tokenize=True,
                                            add_generation_prompt=True)
    else:
        ids = tokenizer(text)["input_ids"]

    stats = GenerationStats()
    t0 = time.perf_counter()
    out = model.generate(
        ids, max_new_tokens=args.n_predict,
        do_sample=args.temperature > 0, temperature=args.temperature,
        top_k=args.top_k, top_p=args.top_p, stats=stats)
    wall = time.perf_counter() - t0
    new = list(out[0][len(ids):])
    text_out = (" ".join(map(str, new)) if tokenizer is None
                else tokenizer.decode(new, skip_special_tokens=True))
    if history is not None:
        history.append({"role": "assistant", "content": text_out})
    if args.stats:
        n = max(len(new) - 1, 1)
        print(f"[first {stats.first_token_s*1e3:.0f} ms | "
              f"rest {stats.rest_cost_mean*1e3:.1f} ms/tok | "
              f"{len(new)} tokens in {wall:.1f}s]", file=sys.stderr)
    return text_out


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from bigdl_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    model, tokenizer = _load(args)

    if args.prompt is not None:
        print(_generate(model, tokenizer, args.prompt, args))
        return 0

    print("interactive chat — empty line or /exit to quit")
    history = []
    while True:
        try:
            line = input("user> ").strip()
        except (EOFError, KeyboardInterrupt):
            break
        if not line or line == "/exit":
            break
        if line == "/clear":
            history = []
            print("(history cleared)")
            continue
        print("assistant>", _generate(model, tokenizer, line, args,
                                      history=history))
    return 0


if __name__ == "__main__":
    sys.exit(main())
