"""Packed-vs-unpacked throughput A/B on a real parquet corpus.

The reference right-pads every document and reports the waste as its
"training tokens %" metric (reference train.py:253-254); `--pack-sequences`
converts that percentage into throughput. This harness measures the
conversion on the accelerator (it exits non-zero without one): one
synthetic-but-real parquet corpus (variable-length documents,
deterministic), one word-level tokenizer, the REAL driver (`pyrecover_tpu.train.train`) run twice —
unpacked vs packed — and the throughput/token-utilization read from the
driver's own logs (the reference's runtime-measured-metrics stance,
train.py:283-296).

Prints ONE JSON line:
  {"metric": "packed_speedup", "value": R, "unit": "x tok/s",
   "extra": {unpacked: {...}, packed: {...}, platform, ...}}

Run (on the chip):
  python tools/bench_packed.py [--steps 25] [--seq-len 2048] [--batch 8]
"""

import argparse
import json
import logging
import os
import re
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

WORDS = [
    "alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta", "theta",
    "iota", "kappa", "lam", "mu", "nu", "xi", "omicron", "pi",
]


def build_corpus(root, n_docs, mean_words, seed=0):
    """Deterministic variable-length corpus + word-level tokenizer dir.

    The cache is keyed on the corpus parameters (a per-params subdir) and
    validated by a DONE marker written LAST — a mid-write kill (the
    campaign runs this under `timeout`) leaves no marker, so the torn
    cache is wiped and rebuilt instead of wedging every retry."""
    import shutil

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from tokenizers import Tokenizer, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast

    root = Path(root) / f"d{n_docs}_w{mean_words}_s{seed}"
    corpus = root / "corpus.parquet"
    tok_dir = root / "tokenizer"
    done = root / "DONE"
    if done.exists():
        return corpus, tok_dir
    shutil.rmtree(root, ignore_errors=True)  # torn partial build, if any
    root.mkdir(parents=True, exist_ok=True)
    rng = np.random.default_rng(seed)
    # lognormal-ish length mix: plenty of short docs (the padding waste the
    # reference reports) plus occasional row-straddling long ones
    lengths = np.clip(
        rng.lognormal(mean=np.log(mean_words), sigma=0.9, size=n_docs), 8,
        mean_words * 12,
    ).astype(int)
    texts = [
        " ".join(WORDS[int(w) % len(WORDS)] for w in rng.integers(0, 64, n))
        for n in lengths
    ]
    pq.write_table(pa.table({"text": texts}), corpus)
    vocab = {"[PAD]": 0, "[UNK]": 1, "[EOS]": 2}
    for t in WORDS:
        vocab.setdefault(t, len(vocab))
    tok = Tokenizer(models.WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    PreTrainedTokenizerFast(
        tokenizer_object=tok, pad_token="[PAD]", unk_token="[UNK]",
        eos_token="[EOS]",
    ).save_pretrained(tok_dir)
    # jaxlint: disable-next=torn-write -- the marker IS the commit protocol:
    # presence-only, written LAST; a torn marker only forces a rebuild
    done.write_text("ok")  # marker LAST: its presence == complete build
    return corpus, tok_dir


def run_variant(corpus, tok_dir, *, packed, steps, seq_len, batch, workdir):
    """One driver run; returns (tok_s, token_pct) parsed from its logs."""
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.train import train
    from pyrecover_tpu.utils.logging import init_logger

    msgs = []

    class _H(logging.Handler):
        def emit(self, record):
            msgs.append(record.getMessage())

    handler = _H()
    init_logger().addHandler(handler)
    try:
        cfg = TrainConfig(
            dataset=str(corpus), tokenizer_name_or_path=str(tok_dir),
            pack_sequences=packed, sequence_length=seq_len, batch_size=batch,
            training_steps=steps, learning_rate=1e-4, lr_warmup_steps=5,
            checkpoint_dir=str(workdir), checkpoint_frequency=-1,
            experiment_name="pack_ab", logging_frequency=5,
            use_flash_attention=True,
            # all-bf16 like bench.py's headline rows — set on the
            # TrainConfig (its __post_init__ would clobber a model-level
            # dtype override)
            model_dtype="bf16", param_dtype="bf16",
        )
        from pyrecover_tpu.models import presets

        cfg.model = presets.llama_150m(max_seq_len=seq_len)
        cfg.__post_init__()
        train(cfg)
    finally:
        init_logger().removeHandler(handler)
    pat = re.compile(
        r"step (\d+).*?\| ([\d.]+) tok/s.*?\| ([\d.]+)% training tokens"
    )
    rows = [
        (int(m.group(1)), float(m.group(2)), float(m.group(3)))
        for m in (pat.search(x) for x in msgs) if m
    ]
    if not rows:
        raise RuntimeError(f"no throughput lines parsed from {len(msgs)} logs")
    # skip the compile step's window: use the median of the later intervals
    tail = sorted(r[1] for r in rows[1:]) or [rows[-1][1]]
    tok_s = tail[len(tail) // 2]
    return tok_s, rows[-1][2]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=25)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--docs", type=int, default=4000)
    ap.add_argument("--mean-words", type=int, default=700)
    ap.add_argument("--data-dir", default=None,
                    help="corpus cache dir (default: a temp dir)")
    args = ap.parse_args()

    data_dir = args.data_dir or os.path.join(
        tempfile.gettempdir(), "pyrecover_bench_corpus"
    )
    from bench import require_accelerator

    device = require_accelerator("bench_packed")
    corpus, tok_dir = build_corpus(data_dir, args.docs, args.mean_words)
    results = {}
    with tempfile.TemporaryDirectory(prefix="pack_ab_") as wd:
        for packed in (False, True):
            tok_s, pct = run_variant(
                corpus, tok_dir, packed=packed, steps=args.steps,
                seq_len=args.seq_len, batch=args.batch,
                workdir=Path(wd) / ("p" if packed else "u"),
            )
            results["packed" if packed else "unpacked"] = {
                "tok_per_sec": round(tok_s, 1),
                "training_token_pct": pct,
            }
    # the conversion packing exists for: EFFECTIVE training tokens/s (raw
    # positions/s x the fraction that are real training tokens) — raw
    # tok/s counts padded positions the unpacked run wastes
    for r in results.values():
        r["effective_tok_per_sec"] = round(
            r["tok_per_sec"] * r["training_token_pct"] / 100.0, 1
        )
    speedup = (
        results["packed"]["effective_tok_per_sec"]
        / results["unpacked"]["effective_tok_per_sec"]
    )
    print(json.dumps({
        "metric": "packed_speedup",
        "value": round(speedup, 3),
        "unit": "x effective training-tok/s (packed / unpacked, same corpus)",
        "extra": {
            "platform": device.platform,
            "device_kind": device.device_kind,
            "seq_len": args.seq_len,
            "batch_size": args.batch,
            "steps": args.steps,
            **results,
        },
    }))


if __name__ == "__main__":
    main()
