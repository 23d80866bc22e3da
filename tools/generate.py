"""Sample text from a pyrecover_tpu checkpoint (either format).

Beyond-parity utility (the reference has no generation path at all): loads
a checkpoint's params, then decodes greedily or with temperature sampling
through the KV-cached incremental decoder (models/decode.py) — prefill is
one call over the prompt, each new token is an O(1) step, two compiles
total regardless of length.

Usage:
  python tools/generate.py CKPT --model llama-150m --prompt-ids 1,2,3 \
      --max-new-tokens 32 [--temperature 0.8] [--tokenizer NAME --prompt "text"]

Exit codes: 0 = ok, 2 = error.
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def build_state(model_cfg):
    import jax

    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train_state import create_train_state

    tc = TrainConfig(sequence_length=model_cfg.max_seq_len)
    tc.model = model_cfg
    tc.__post_init__()
    optimizer, _ = build_optimizer(tc)
    return create_train_state(jax.random.key(0), tc.model, optimizer), tc.model


def load_params(path, model_cfg):
    if Path(path).is_dir():
        # sharded (Orbax) stores the whole TrainState; restore it all
        from pyrecover_tpu.checkpoint import load_ckpt_sharded

        target, model_cfg = build_state(model_cfg)
        state, _, _ = load_ckpt_sharded(path, target)
        return state.params, model_cfg
    # vanilla: select only the params leaves (".params[...]" key paths) —
    # no need to read Adam moments into memory for a params-only tool
    import jax
    import jax.numpy as jnp

    from pyrecover_tpu.checkpoint.vanilla import read_ckpt_raw
    from pyrecover_tpu.models.llama import init_params

    _, paths, leaves = read_ckpt_raw(path)
    abstract = jax.eval_shape(lambda: init_params(jax.random.key(0), model_cfg))
    p_leaves, treedef = jax.tree_util.tree_flatten(abstract)
    picked = [
        leaf for kp, leaf in zip(paths, leaves) if kp.startswith(".params")
    ]
    if len(picked) != len(p_leaves):
        raise ValueError(
            f"checkpoint has {len(picked)} params leaves, model expects "
            f"{len(p_leaves)} — wrong --model shape?"
        )
    params = jax.tree_util.tree_unflatten(
        treedef,
        [jnp.asarray(l).astype(t.dtype) for l, t in zip(picked, p_leaves)],
    )
    return params, model_cfg


def generate(params, model_cfg, rows, max_new_tokens, temperature, seed):
    """``rows``: a validated list of one-or-more EQUAL-length prompt rows
    (the caller normalizes/validates — a batch decodes in lockstep through
    one cache, one model pass per token regardless of batch size).
    Returns a list of output rows, one per prompt."""
    from pyrecover_tpu.models.decode import generate_tokens

    # the cache covers max_seq_len positions; the library API raises on
    # overflow, but the CLI clamps like the old sliding-window behavior:
    # keep the prompt TAIL and cap the new-token budget, with a warning
    L = model_cfg.max_seq_len
    max_new_tokens = int(max_new_tokens)
    if max_new_tokens >= L:
        print(f"warning: --max-new-tokens capped to {L - 1} "
              f"(max-seq-len {L})", file=sys.stderr)
        max_new_tokens = L - 1
    dropped = [[] for _ in rows]
    if len(rows[0]) + max_new_tokens > L:
        keep = L - max_new_tokens
        dropped = [r[:-keep] for r in rows]
        print(f"warning: prompt truncated to its last {keep} tokens to fit "
              f"max-seq-len {L} with {max_new_tokens} new tokens",
              file=sys.stderr)
        rows = [r[-keep:] for r in rows]
    out = generate_tokens(
        params, model_cfg, rows if len(rows) > 1 else rows[0],
        max_new_tokens, temperature=temperature, seed=seed,
    )
    if len(rows) == 1:
        out = [out]
    return [d + o for d, o in zip(dropped, out)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("checkpoint", help="vanilla .ckpt file or sharded dir")
    ap.add_argument("--model", default="llama-150m",
                    help="preset name (models/presets.py)")
    ap.add_argument("--vocab-size", type=int, default=0,
                    help="override preset vocab (must match the checkpoint)")
    ap.add_argument("--model-dim", type=int, default=0,
                    help="with --model-layers/--model-heads/--model-kv-heads:"
                         " build a custom shape instead of a preset")
    ap.add_argument("--model-layers", type=int, default=0)
    ap.add_argument("--model-heads", type=int, default=0)
    ap.add_argument("--model-kv-heads", type=int, default=0)
    ap.add_argument("--max-seq-len", type=int, default=0)
    ap.add_argument("--multiple-of", type=int, default=0)
    ap.add_argument("--model-loop-steps", type=int, default=1,
                    help="a looped checkpoint: the trainer's flags of the "
                         "same names (custom shape only)")
    ap.add_argument("--model-post-norms", action="store_true")
    ap.add_argument("--model-exit-gate", action="store_true")
    ap.add_argument("--model-tie-embeddings", action="store_true",
                    help="the checkpoint's head is its embedding table")
    ap.add_argument("--model-no-rope", action="store_true")
    ap.add_argument("--model-attn-period", type=int, default=1,
                    help="a hybrid checkpoint (Mamba layers): refused in "
                         "words, the key/value cache holds no recurrent "
                         "state")
    ap.add_argument("--model-attn-offset", type=int, default=0)
    ap.add_argument("--prompt-ids", default="1",
                    help="comma-separated token ids; ';' separates a BATCH "
                         "of equal-length prompts decoded in lockstep "
                         "(one output line per prompt)")
    ap.add_argument("--prompt", default="",
                    help="text prompt (requires --tokenizer)")
    ap.add_argument("--tokenizer", default="",
                    help="HF tokenizer name/path for --prompt and decoding")
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import dataclasses

        from pyrecover_tpu.models import presets
        from pyrecover_tpu.models.llama import ModelConfig

        shape_flags = (args.model_layers, args.model_heads, args.model_kv_heads)
        if args.model_dim:
            cfg = ModelConfig(
                dim=args.model_dim, n_layers=args.model_layers,
                n_heads=args.model_heads, n_kv_heads=args.model_kv_heads,
                vocab_size=args.vocab_size or 32768,
                max_seq_len=args.max_seq_len or 2048,
                multiple_of=args.multiple_of or 1024,
                loop_steps=args.model_loop_steps,
                post_norms=args.model_post_norms,
                exit_gate=args.model_exit_gate,
                tie_embeddings=args.model_tie_embeddings,
                rope=not args.model_no_rope,
                attn_layer_period=args.model_attn_period,
                attn_layer_offset=args.model_attn_offset,
            )
        else:
            if any(shape_flags) or args.multiple_of:
                print("--model-layers/-heads/-kv-heads/--multiple-of require "
                      "--model-dim (custom shape)", file=sys.stderr)
                return 2
            cfg = presets.PRESETS[args.model]()
            if args.max_seq_len:
                # must match the sequence length the model was trained with
                cfg = dataclasses.replace(cfg, max_seq_len=args.max_seq_len)
        if args.vocab_size:
            cfg = dataclasses.replace(cfg, vocab_size=args.vocab_size)

        tokenizer = None
        if args.tokenizer:
            from pyrecover_tpu.data.parquet import load_tokenizer

            tokenizer = load_tokenizer(args.tokenizer)
        if args.prompt:
            if tokenizer is None:
                print("--prompt requires --tokenizer", file=sys.stderr)
                return 2
            rows = [tokenizer(args.prompt)["input_ids"]]
        else:
            groups = [g for g in args.prompt_ids.split(";") if g]
            rows = [[int(x) for x in g.split(",") if x] for g in groups]
        # validate HERE, before the tail-truncation could silently equalize
        # a ragged batch the library would have rejected loudly
        if not rows or any(not r for r in rows):
            print("error: every prompt needs at least one token id",
                  file=sys.stderr)
            return 2
        if any(len(r) != len(rows[0]) for r in rows):
            print("error: batched prompts must be EQUAL length "
                  f"(got {[len(r) for r in rows]})", file=sys.stderr)
            return 2

        params, cfg = load_params(args.checkpoint, cfg)
        out_rows = generate(params, cfg, rows, args.max_new_tokens,
                            args.temperature, args.seed)
        for row in out_rows:
            if tokenizer is not None:
                print(tokenizer.decode(row))
            else:
                print(",".join(str(i) for i in row))
        return 0
    except Exception as e:  # tool: fail with a message, not a traceback wall
        print(f"error: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
