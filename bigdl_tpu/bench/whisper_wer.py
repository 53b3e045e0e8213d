"""Whisper WER evaluation harness.

Role of the reference's whisper benchmark (reference
dev/benchmark/whisper/run_whisper.py: librispeech test split through
`AutoModelForSpeechSeq2Seq.from_pretrained(load_in_low_bit=...)`,
word-error-rate via the `evaluate` package, per-sample wall time to
CSV). Differences by design:

- the WER metric is implemented here (plain word-level edit distance) —
  no `evaluate`/`jiwer` dependency, and it is unit-testable offline;
- the dataset is pluggable: `--dataset librispeech` uses HF `datasets`
  when installed (the reference's path), `--dataset dir:<path>` reads
  (x.npy [n_mels, T] precomputed log-mel + x.txt transcript) pairs so a
  WER run needs nothing beyond numpy;
- results stream to CSV the same shape the reference's
  whisper_csv_to_html.py consumes (model, data_type, WER, mean latency).

Run: python -m bigdl_tpu.bench.whisper_wer --model_path <whisper-ckpt>
         --load_in_low_bit sym_int4 --dataset dir:/data/asr_pairs
"""

from __future__ import annotations

import argparse
import csv
import os
import time
from typing import Iterable, List, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# WER metric (word-level Levenshtein, the `evaluate`-package definition)
# ---------------------------------------------------------------------------


def _normalize(text: str) -> List[str]:
    """The reference normalizes with WhisperProcessor's tokenizer
    cleanup; offline we lowercase and strip punctuation to spaces."""
    out = []
    for word in text.lower().split():
        w = "".join(c for c in word if c.isalnum() or c == "'")
        if w:
            out.append(w)
    return out


def wer(references: Iterable[str], hypotheses: Iterable[str]) -> float:
    """Corpus WER: total word edits / total reference words."""
    edits = 0
    ref_words = 0
    for ref, hyp in zip(references, hypotheses):
        r, h = _normalize(ref), _normalize(hyp)
        ref_words += len(r)
        # single-row DP over the shorter dimension
        prev = list(range(len(h) + 1))
        for i, rw in enumerate(r, 1):
            cur = [i] + [0] * len(h)
            for j, hw in enumerate(h, 1):
                cur[j] = min(prev[j] + 1, cur[j - 1] + 1,
                             prev[j - 1] + (rw != hw))
            prev = cur
        edits += prev[-1]
    if ref_words == 0:
        return 0.0
    return edits / ref_words


# ---------------------------------------------------------------------------
# Dataset adapters
# ---------------------------------------------------------------------------


def iter_dir_dataset(path: str) -> Iterable[Tuple[np.ndarray, str]]:
    """(features, transcript) pairs from a directory of x.npy + x.txt.
    .npy files hold [n_mels, T] log-mel features (precomputed)."""
    for name in sorted(os.listdir(path)):
        if not name.endswith(".npy"):
            continue
        stem = name[:-4]
        txt = os.path.join(path, stem + ".txt")
        if not os.path.exists(txt):
            continue
        feats = np.load(os.path.join(path, name))
        with open(txt) as f:
            yield feats, f.read().strip()


def iter_librispeech(data_type: str, n: int, model_path: str):
    """The reference's dataset path; needs `datasets` + a processor."""
    try:
        from datasets import load_dataset
        from transformers import WhisperProcessor
    except ImportError as e:
        raise RuntimeError(
            "librispeech mode needs the `datasets` package and a local "
            "WhisperProcessor; use --dataset dir:<path> for offline "
            "runs") from e
    ds = load_dataset("librispeech_asr", name=data_type,
                      split="test").select(range(n))
    proc = WhisperProcessor.from_pretrained(model_path)
    for sample in ds:
        feats = proc(sample["audio"]["array"],
                     sampling_rate=sample["audio"]["sampling_rate"],
                     return_tensors="np").input_features[0]
        yield feats, sample["text"]


# ---------------------------------------------------------------------------
# Evaluation loop
# ---------------------------------------------------------------------------


def evaluate_wer(model, tokenizer, dataset, max_new_tokens: int = 128,
                 forced_ids: Tuple[int, ...] = ()) -> dict:
    """Transcribe every (features, transcript) pair; returns
    {wer, mean_latency_ms, first_latency_ms, n}."""
    refs: List[str] = []
    hyps: List[str] = []
    times: List[float] = []
    # the reference passes processor.get_decoder_prompt_ids() as
    # forced_decoder_ids [(pos, id), ...]; our generate takes the full
    # forced prefix as decoder_input_ids ([start] + forced)
    prefix = None
    if forced_ids:
        start = model.config.decoder_start_token_id
        prefix = np.asarray(
            [[start] + [t for _, t in sorted(forced_ids)]], np.int32)
    for feats, text in dataset:
        mel = np.asarray(feats, np.float32)[None]      # [1, n_mels, T]
        t0 = time.perf_counter()
        ids = np.asarray(model.generate(
            mel, decoder_input_ids=prefix,
            max_new_tokens=max_new_tokens))[0]
        times.append((time.perf_counter() - t0) * 1e3)
        hyp = tokenizer.decode(ids, skip_special_tokens=True) \
            if tokenizer is not None else " ".join(map(str, ids))
        refs.append(text)
        hyps.append(hyp)
    return {
        "wer": wer(refs, hyps),
        "n": len(refs),
        "first_latency_ms": times[0] if times else 0.0,
        "mean_latency_ms": (sum(times[1:]) / max(len(times) - 1, 1)
                            if len(times) > 1 else
                            (times[0] if times else 0.0)),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Whisper WER + latency (reference run_whisper.py)")
    ap.add_argument("--model_path", required=True)
    ap.add_argument("--load_in_low_bit", default="sym_int4")
    ap.add_argument("--dataset", default="librispeech",
                    help="'librispeech' (needs datasets pkg), "
                    "'dir:<path>' for local .npy/.txt pairs")
    ap.add_argument("--data_type", default="clean")
    ap.add_argument("--n", type=int, default=500)
    ap.add_argument("--max_new_tokens", type=int, default=128)
    ap.add_argument("--save_result", action="store_true")
    ap.add_argument("--out_csv", default="whisper_wer.csv")
    args = ap.parse_args(argv)

    from bigdl_tpu.transformers import AutoModelForSpeechSeq2Seq

    model = AutoModelForSpeechSeq2Seq.from_pretrained(
        args.model_path, load_in_low_bit=args.load_in_low_bit)
    tokenizer = None
    processor = None
    try:
        from transformers import WhisperProcessor

        processor = WhisperProcessor.from_pretrained(args.model_path)
        tokenizer = processor.tokenizer
    except Exception:
        pass

    if args.dataset.startswith("dir:"):
        data = iter_dir_dataset(args.dataset[4:])
    else:
        data = iter_librispeech(args.data_type, args.n, args.model_path)

    # the reference forces <|lang|><|task|> via the processor's decoder
    # prompt ids (run_whisper.py get_decoder_prompt_ids) — without them
    # a multilingual checkpoint may pick the wrong task
    forced = ()
    if processor is not None:
        try:
            forced = tuple(processor.get_decoder_prompt_ids(
                language="en", task="transcribe"))
        except Exception:
            forced = ()

    res = evaluate_wer(model, tokenizer, data,
                       max_new_tokens=args.max_new_tokens,
                       forced_ids=forced)
    if res["n"] == 0:
        raise SystemExit(
            "dataset yielded 0 samples — dir mode needs paired "
            "<stem>.npy (log-mel [n_mels, T]) + <stem>.txt files")
    print(f"WER {res['wer']:.4f} over {res['n']} samples; "
          f"first {res['first_latency_ms']:.0f} ms, "
          f"mean {res['mean_latency_ms']:.0f} ms")
    if args.save_result:
        new = not os.path.exists(args.out_csv)
        with open(args.out_csv, "a", newline="") as f:
            w = csv.writer(f)
            if new:
                w.writerow(["model", "low_bit", "data", "n", "WER",
                            "first_ms", "mean_ms"])
            w.writerow([os.path.basename(args.model_path.rstrip("/")),
                        args.load_in_low_bit, args.dataset, res["n"],
                        f"{res['wer']:.4f}",
                        f"{res['first_latency_ms']:.1f}",
                        f"{res['mean_latency_ms']:.1f}"])
        print(f"appended to {args.out_csv}")
    return res


if __name__ == "__main__":
    main()
