"""QLoRA finetuning recipe: the alpaca-qlora example, TPU-native.

Equivalent of the reference's flagship finetuning example
(reference example/GPU/LLM-Finetuning/QLoRA/alpaca-qlora/
alpaca_qlora_finetuning.py + deepspeed_zero2.json + mpirun launchers;
call stack SURVEY.md §3.5). The mpirun/oneCCL/ZeRO-2 stack collapses into
a dp-sharded jit step; multi-host pods need only `jax.distributed`.

    python -m bigdl_tpu.examples.qlora_finetune \
        --base-model /path/Llama-2-7b-hf --data-path alpaca.json \
        --low-bit nf4 --steps 500 --dp 4

Data: a JSON list of {"instruction", "input", "output"} (alpaca format) or
{"text"}; tokenized with the model's tokenizer, packed to --seq-len.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, Iterator, List

import numpy as np


def format_alpaca(rec: Dict[str, Any]) -> str:
    if "text" in rec:
        return rec["text"]
    instr = rec.get("instruction", "")
    inp = rec.get("input", "")
    out = rec.get("output", "")
    if inp:
        return (f"Below is an instruction that describes a task, paired "
                f"with an input.\n\n### Instruction:\n{instr}\n\n"
                f"### Input:\n{inp}\n\n### Response:\n{out}")
    return (f"Below is an instruction that describes a task.\n\n"
            f"### Instruction:\n{instr}\n\n### Response:\n{out}")


def pack_batches(token_streams: List[List[int]], batch: int, seq_len: int,
                 pad_id: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    """Greedy-pack tokenized records into fixed [batch, seq_len] blocks."""
    import itertools

    flat = list(itertools.chain.from_iterable(token_streams))
    n_per = batch * seq_len
    for i in range(0, len(flat) - n_per + 1, n_per):
        ids = np.asarray(flat[i:i + n_per], np.int32).reshape(batch, seq_len)
        yield {"input_ids": ids,
               "attention_mask": np.ones_like(ids)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--base-model", required=True)
    ap.add_argument("--data-path", required=True)
    ap.add_argument("--output-dir", default="./qlora-out")
    ap.add_argument("--low-bit", default="nf4")
    ap.add_argument("--lora-r", type=int, default=8)
    ap.add_argument("--lora-alpha", type=float, default=16.0)
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel ways over the device mesh")
    ap.add_argument("--relora-steps", type=int, default=0,
                    help="merge-restart interval (0 = plain QLoRA)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import optax

    from bigdl_tpu.config import enable_compilation_cache
    from bigdl_tpu.qlora import LoraConfig, attach_lora, lora_trainable_mask
    from bigdl_tpu.relora import relora_restart
    from bigdl_tpu.training import make_lora_train_step, partition, combine
    from bigdl_tpu.transformers.model import AutoModelForCausalLM

    enable_compilation_cache()
    # split projection layout: the LoRA targets name q_proj/k_proj/...
    model = AutoModelForCausalLM.from_pretrained(
        args.base_model, load_in_low_bit=args.low_bit,
        merge_projections=False)
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(args.base_model)

    records = json.load(open(args.data_path))
    streams = [tok(format_alpaca(r))["input_ids"] for r in records]
    batches = pack_batches(streams, args.batch, args.seq_len)

    lcfg = LoraConfig(r=args.lora_r, lora_alpha=args.lora_alpha)
    params = attach_lora(model.params, lcfg, key=jax.random.PRNGKey(0))
    mask = lora_trainable_mask(params)
    train, frozen = partition(params, mask)
    optimizer = optax.adamw(args.lr)
    opt_state = optimizer.init(train)
    step = make_lora_train_step(model.family.forward_train, model.config,
                                optimizer)

    if args.dp > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[: args.dp]), ("dp",))
        spec = NamedSharding(mesh, P("dp"))

        def shard(b):
            return {k: jax.device_put(jnp.asarray(v), spec)
                    for k, v in b.items()}
    else:
        def shard(b):
            return {k: jnp.asarray(v) for k, v in b.items()}

    t0 = time.time()
    key = jax.random.PRNGKey(1)
    for i, batch in enumerate(batches):
        if i >= args.steps:
            break
        if args.relora_steps and i > 0 and i % args.relora_steps == 0:
            key, sub = jax.random.split(key)
            train, frozen, opt_state, mask = relora_restart(
                train, frozen, optimizer, lcfg, key=sub)
        train, opt_state, loss = step(train, opt_state, frozen, shard(batch))
        if i % 10 == 0:
            print(f"step {i}: loss {float(loss):.4f} "
                  f"({time.time() - t0:.0f}s)", flush=True)

    # persist: merged low-bit model (adapters folded in)
    from bigdl_tpu.qlora import merge_lora

    model.params = merge_lora(combine(train, frozen))
    model.save_low_bit(args.output_dir)
    print(f"merged model saved to {args.output_dir}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
