"""Configuration: one dataclass surface + CLI parser.

Flag parity with the reference's single argparse surface (`utils.py:105-261`,
25 flags) — same flag strings wherever the concept survives the TPU
re-design, plus TPU-native extensions (mesh shape, fsdp/tensor/sequence
axes, remat, synthetic data). Torch-specific flags are kept as accepted
aliases so reference launch lines keep working:

  * ``--fused-optimizer`` / ``--compile`` → accepted no-ops (XLA always
    compiles and fuses the optimizer into the step).
  * ``--use_flash_attention`` → selects the Pallas flash-attention kernel.
  * ``--distributed`` → requires a multi-host env: a failed or absent
    rendezvous is FATAL (reference dist_utils.py:64-65 exits hard), never
    a silent fall-back to N divergent single-process runs.
"""

import argparse
import dataclasses
from typing import Optional

from pyrecover_tpu.checkpoint.engine import ENGINES
from pyrecover_tpu.models.llama import ModelConfig
from pyrecover_tpu.parallel.mesh import MeshConfig


@dataclasses.dataclass
class TrainConfig:
    # -- data ----------------------------------------------------------------
    dataset: str = ""  # path to parquet with a 'text' column; "" → synthetic
    tokenizer_name_or_path: str = "unsloth/Mistral-Nemo-Base-2407-bnb-4bit"
    # pack multiple documents per row (segment-id attention masking) instead
    # of right-padding each one like the reference (dataset.py:29-35) —
    # training-tokens % becomes ~100 by construction
    pack_sequences: bool = False
    # seconds without a batch before the loader raises LoaderStallError
    # instead of wedging the step loop forever; 0 disables the watchdog
    loader_stall_timeout: float = 0.0
    sequence_length: int = 2048
    batch_size: int = 1  # GLOBAL batch size (reference train.py:62-63 semantics)
    training_samples: int = 0  # 0 → len(dataset); else wraparound like ref dataset.py:25
    # -- optimization --------------------------------------------------------
    learning_rate: float = 1e-5
    lr_warmup_steps: int = 10
    lr_schedule: str = "constant"  # "constant" (reference) | "cosine"
    lr_min_ratio: float = 0.1  # cosine floor as a fraction of peak LR
    grad_accumulation_steps: int = 1  # micro-steps per optimizer update
    weight_decay: float = 0.1
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    grad_max_norm: float = 1.0
    grad_clipping: bool = True  # the reference defines but disables clipping (train.py:272)
    # -- bandwidth-lean update path (README "Bandwidth-lean update path") -----
    # "zero1": shard the AdamW moments (and the weight-update compute)
    # across the data axis — reduce-scatter(grads) -> shard-local update
    # -> allgather(updates), all inside the one jitted step; optimizer
    # HBM per device drops by the data-axis size, and fp32 collectives
    # stay bit-exact vs "none" (test- and chaos-gated)
    optimizer_sharding: str = "none"  # none | zero1
    # gradient-sync wire format over the data axis: fp32 (the implicit
    # GSPMD allreduce), bf16 (cast, no feedback — the ablation baseline),
    # or int8 (block-scaled with per-replica error-feedback residuals
    # carried in the train state; parallel/collectives.py)
    grad_allreduce: str = "fp32"  # fp32 | bf16 | int8
    grad_quant_block: int = 256  # int8 block size (one f32 scale per block)
    # latency-hidden gradients: >0 partitions the flattened gradient
    # pytree into fixed-byte buckets (reverse-autodiff order) and issues
    # one data-axis collective per bucket, so XLA overlaps each bucket's
    # wire time with the remaining backward compute. 0 = one tail-of-
    # backward sync (the PR 10 form). Composes with fp32 (per-bucket
    # psum, bit-exact vs unbucketed), bf16/int8 (per-bucket quantized
    # legs + re-blocked error feedback), zero1 and grad accumulation.
    grad_bucket_mb: float = 0.0
    training_steps: int = 1000
    seed: int = 42
    # -- model ---------------------------------------------------------------
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    model_dtype: str = "bf16"  # compute dtype (reference --model-dtype)
    param_dtype: str = "fp32"  # master weights; TPU-native improvement over all-bf16
    use_flash_attention: bool = False
    # "auto": ring when --sp > 1 (sequence-sharded ppermute ring — the
    # long-context path), else flash if --use_flash_attention, else sdpa
    attention_impl: str = "auto"  # auto | sdpa | flash | ring
    remat: bool = False
    pp_microbatches: int = 0  # pipeline microbatches; 0 → stage count
    # "gpipe": AD-derived backward wave (composes with everything);
    # "1f1b": explicit interleaved backward — bounds in-flight microbatch
    # activations per stage to the stage count (parallel/pipeline.py).
    # None = unset: defer to the model config (so an explicit CLI value is
    # distinguishable from the default and always wins)
    pp_schedule: Optional[str] = None
    # interleaved 1F1B chunks per stage (1f1b only); None = defer to model
    pp_virtual_stages: Optional[int] = None
    loss_chunk_size: int = 0  # >0: fused chunked CE, never materializes full logits
    # -- parallelism ---------------------------------------------------------
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    distributed: bool = False  # demand a multi-host rendezvous (hard-fail without one)
    # -- checkpointing -------------------------------------------------------
    checkpoint_dir: str = "checkpoints/"
    # save every k steps; any value < 1 disables periodic saves and is
    # normalized to the canonical -1 in __post_init__ (the docs used to
    # say "-1 disables" while train.py gated on > 0, so 0 and other
    # negatives silently disabled too — now they disable LOUDLY). The CLI
    # also accepts --checkpoint-frequency auto (checkpoint_auto below):
    # the goodput autopilot then adapts the interval online and this
    # value only serves as the static baseline for the counterfactual
    checkpoint_frequency: int = 10
    # telemetry-driven adaptive cadence (resilience/autopilot.py): compute
    # the Young-Daly optimal save interval online from the observed
    # per-save blocking cost and the interruption rate persisted in the
    # failure-history sidecar; bounded by the floor/ceiling below, with
    # hysteresis so one outlier cannot thrash the cadence
    checkpoint_auto: bool = False
    ckpt_auto_floor: int = 1  # hard minimum interval (steps)
    ckpt_auto_ceiling: int = 500  # hard maximum interval (steps)
    # MTTI assumed while ZERO interruptions have been observed (the
    # bounded prior the interval degrades to — saves are never disabled)
    ckpt_auto_mtti_prior_s: float = 3600.0
    ckpt_auto_window: int = 8  # interruptions in the windowed MTTI estimate
    resume_from_checkpoint: Optional[str] = None  # path | "latest"
    experiment_name: str = "default-exp"
    verify_checkpoints: bool = False
    max_kept_checkpoints: int = 3
    # which engine writes checkpoints: "vanilla" (single-file streaming),
    # "sharded" (Orbax/tensorstore), or "zerostall" (async snapshot
    # pipeline + content-addressed chunk store + in-RAM emergency tier,
    # checkpoint/zerostall/); checkpoint/engine.py maps the name
    checkpoint_engine: str = "vanilla"  # vanilla | sharded | zerostall
    async_checkpoint: bool = True  # overlap saves with training
    # topology-elastic resume (checkpoint/elastic.py): "auto" reshards a
    # checkpoint saved on a different topology onto the live mesh (after a
    # mandatory shardcheck preflight), "on" always runs the elastic gate,
    # "off" fails loud with TopologyMismatchError on any topology drift
    elastic_resume: str = "auto"  # auto | on | off
    # -- time-aware checkpointing / preemption -------------------------------
    timeaware_checkpointing: bool = False
    default_iter_time: float = 1.0
    default_ckpt_time: float = 10.0
    job_end_time: Optional[float] = None  # unix seconds; else $JOB_END_TIME / SLURM_JOB_END_TIME
    # the deadline decision (device sync + cross-host broadcast) runs every
    # k-th step; the safety buffer absorbs the ≤(k-1)-step decision delay.
    # Cheap host-local preemption signals are still observed every step.
    preempt_check_interval: int = 5
    # -- evaluation (beyond-parity: the reference has no eval loop) ----------
    eval_frequency: int = 0  # every k steps; 0 disables
    eval_samples: int = 64  # held-out sample count per evaluation
    eval_dataset: str = ""  # parquet path; "" → held-out synthetic split
    # -- observability -------------------------------------------------------
    logging_frequency: int = 5
    log_loss_to_csv: bool = False
    # structured telemetry (pyrecover_tpu/telemetry): host-0 JSONL event
    # stream with step timing, checkpoint lifecycle, preemption, and
    # run-summary goodput events; tools/summarize_telemetry.py reads it
    telemetry: bool = False
    telemetry_path: str = ""  # "" → <ckpt_dir>/<exp>/<exp>_telemetry.jsonl
    telemetry_stdout: bool = False  # mirror events into the host-0 text log
    # seconds between metrics_snapshot flushes (counters/gauges/histogram
    # percentiles from telemetry/metrics.py); flushed at sync points only
    metrics_flush_interval_s: float = 30.0
    # run-health watchdog (telemetry/watchdog.py): seconds of NO progress
    # (train loop, loader workers, checkpoint writer all silent) before a
    # hang_detected event + a flight-recorder bundle are written — the run
    # is never killed. 0 disables. Monitoring starts after the first
    # completed step, so first-step compile time cannot false-trip it.
    hang_watchdog_timeout: float = 0.0
    # implicit host-transfer detection around the jitted step dispatch
    # (telemetry/detectors.py): "log" = jax.transfer_guard("log") over the
    # hot loop (stderr only); "disallow" = per-dispatch guard that emits an
    # implicit_transfer event and raises ImplicitTransferError
    transfer_guard: str = "off"  # off | log | disallow
    profile: bool = False
    profile_step_start: int = 10
    profile_step_end: int = 12
    # where --profile writes; relative = under the experiment directory
    profile_dir: str = "profiles/"

    def __post_init__(self):
        if self.optimizer_sharding not in ("none", "zero1"):
            raise ValueError(
                f"unknown --optimizer-sharding {self.optimizer_sharding!r} "
                "(expected none or zero1)"
            )
        if self.grad_allreduce not in ("fp32", "bf16", "int8"):
            raise ValueError(
                f"unknown --grad-allreduce {self.grad_allreduce!r} "
                "(expected fp32, bf16 or int8)"
            )
        if self.model.loop_steps > 1 and self.mesh.pipeline > 1:
            from pyrecover_tpu.models.llama import refuse_looped

            # both schedules: a stage would have to run loop_steps times a
            # step, which neither gpipe nor 1f1b (parallel/pipeline.py) does
            refuse_looped(self.model, "pipeline parallelism (--pp > 1)")
        if self.model.hybrid:
            from pyrecover_tpu.models.llama import refuse_hybrid

            # where the model is built, in words, never inside a trace
            if self.mesh.pipeline > 1:
                refuse_hybrid(self.model, "pipeline parallelism (--pp > 1)")
            if self.pack_sequences:
                raise ValueError(
                    "--pack-sequences cannot train a hybrid stack "
                    "(--model-attn-period > 1): the selective scan carries "
                    "its state across a document boundary (ROADMAP.md M3)"
                )
        if self.grad_quant_block <= 0:
            raise ValueError(
                f"--grad-quant-block must be positive, got "
                f"{self.grad_quant_block}"
            )
        if self.grad_bucket_mb < 0:
            raise ValueError(
                f"--grad-bucket-mb must be >= 0, got {self.grad_bucket_mb}"
            )
        if self.grad_allreduce != "fp32" or self.grad_bucket_mb > 0:
            # the explicit gradient sync (quantized collectives and/or
            # bucketed overlap) runs its own shard_map manual over the
            # data axis; schedules/axes with their OWN manual regions
            # would nest inside it — rejected loudly instead of tracing
            # into an unsupported composition
            lean = (
                f"--grad-allreduce {self.grad_allreduce}"
                if self.grad_allreduce != "fp32" else "--grad-bucket-mb"
            )
            if self.pp_schedule == "1f1b" or self.mesh.pipeline > 1:
                raise ValueError(
                    f"{lean} does not compose with pipeline parallelism "
                    "(the pipeline schedule runs its own manual region); "
                    "drop it with --pp"
                )
            if self.mesh.sequence > 1:
                raise ValueError(
                    f"{lean} does not compose with sequence parallelism "
                    "(ring attention runs its own manual region); drop "
                    "it with --sp"
                )
            if (
                self.mesh.fsdp > 1 or self.mesh.tensor > 1
                or self.mesh.expert > 1
            ):
                # params sharded over fsdp/tensor/expert inside the
                # data-manual sync region hit XLA's partial-manual
                # partitioner weakness (hard CHECK failure, the same one
                # models/moe.py and train_state._token_logprob document)
                raise ValueError(
                    f"{lean} supports pure data-parallel replicas "
                    "(+zero1) only; fsdp/tensor/expert axes already "
                    "shard their own collectives — drop it with them"
                )
        # normalize the disable sentinel: the docs promise "-1 disables",
        # and train.py gates on > 0 — so 0 and other negatives used to
        # disable silently. Any value < 1 now canonicalizes to -1 with a
        # loud one-time note, so "my checkpoints never saved" is always
        # diagnosable from the log.
        if self.checkpoint_frequency < 1:
            if self.checkpoint_frequency != -1:
                import logging

                logging.getLogger("pyrecover_tpu").warning(
                    "--checkpoint-frequency %d disables periodic "
                    "checkpoints (any value < 1 does; normalized to -1)",
                    self.checkpoint_frequency,
                )
            self.checkpoint_frequency = -1
        if self.ckpt_auto_floor < 1:
            raise ValueError(
                f"--ckpt-auto-floor must be >= 1, got {self.ckpt_auto_floor}"
            )
        if self.ckpt_auto_ceiling < self.ckpt_auto_floor:
            raise ValueError(
                f"--ckpt-auto-ceiling {self.ckpt_auto_ceiling} must be >= "
                f"--ckpt-auto-floor {self.ckpt_auto_floor}"
            )
        if self.ckpt_auto_mtti_prior_s <= 0:
            raise ValueError(
                "--ckpt-auto-mtti-prior must be positive, got "
                f"{self.ckpt_auto_mtti_prior_s}"
            )
        if self.ckpt_auto_window < 1:
            raise ValueError(
                f"--ckpt-auto-window must be >= 1, got {self.ckpt_auto_window}"
            )
        if self.checkpoint_engine not in ENGINES:
            raise ValueError(
                f"unknown checkpoint engine {self.checkpoint_engine!r}"
            )
        if self.attention_impl == "auto":
            if self.mesh.sequence > 1:
                attn = "ring"
            elif self.use_flash_attention:
                attn = "flash"
            else:
                attn = self.model.attention_impl
        else:
            attn = self.attention_impl
        self.model = dataclasses.replace(
            self.model,
            max_seq_len=self.sequence_length,
            compute_dtype={"bf16": "bfloat16", "fp16": "float16", "fp32": "float32",
                           "fp64": "float64"}.get(self.model_dtype, self.model_dtype),
            param_dtype={"bf16": "bfloat16", "fp16": "float16", "fp32": "float32",
                         "fp64": "float64"}.get(self.param_dtype, self.param_dtype),
            attention_impl=attn,
            remat=self.remat or self.model.remat,
            pp_microbatches=self.pp_microbatches or self.model.pp_microbatches,
            # unset (None) defers to a model-set value (presets / test
            # configs set these on the model directly); an explicit value —
            # even the default string — wins
            pp_schedule=(
                self.pp_schedule
                if self.pp_schedule is not None
                else self.model.pp_schedule
            ),
            pp_virtual_stages=(
                self.pp_virtual_stages
                if self.pp_virtual_stages is not None
                else self.model.pp_virtual_stages
            ),
        )


def _checkpoint_frequency_arg(value):
    """``--checkpoint-frequency`` accepts an int (every k steps; < 1
    disables) or the literal ``auto`` (goodput autopilot adapts it)."""
    v = str(value).strip().lower()
    if v == "auto":
        return "auto"
    try:
        return int(v)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected an integer or 'auto', got {value!r}"
        )


def build_parser():
    p = argparse.ArgumentParser(
        description="pyrecover_tpu trainer",
        fromfile_prefix_chars="@",
    )
    d = TrainConfig()

    # data (reference utils.py:107-118)
    p.add_argument("--dataset", type=str, default=d.dataset,
                   help="Parquet file with a 'text' column. Empty → deterministic synthetic data.")
    p.add_argument("--tokenizer-name-or-path", type=str, default=d.tokenizer_name_or_path)
    p.add_argument("--pack-sequences", action="store_true",
                   help="Pack multiple documents per row (segment-masked "
                        "attention) instead of right-padding each one; "
                        "training-tokens %% becomes ~100.")
    p.add_argument("--loader-stall-timeout", type=float,
                   default=d.loader_stall_timeout,
                   help="Seconds without a batch before the data loader "
                        "raises LoaderStallError (emitting a "
                        "loader_stall_timeout telemetry event) instead of "
                        "hanging the step loop. 0 disables the watchdog.")
    p.add_argument("--sequence-length", type=int, default=d.sequence_length)
    p.add_argument("--batch-size", type=int, default=d.batch_size,
                   help="GLOBAL batch size, sharded over the data axis.")
    p.add_argument("--training-samples", type=int, default=d.training_samples)

    # optimization (utils.py:133-151, 171-175)
    p.add_argument("--learning-rate", type=float, default=d.learning_rate)
    p.add_argument("--lr-warmup-steps", type=int, default=d.lr_warmup_steps)
    p.add_argument("--lr-schedule", type=str, default=d.lr_schedule,
                   choices=["constant", "cosine"],
                   help="constant after warmup (reference) or cosine decay "
                        "to --lr-min-ratio over --training-steps.")
    p.add_argument("--lr-min-ratio", type=float, default=d.lr_min_ratio)
    p.add_argument("--grad-accumulation-steps", type=int,
                   default=d.grad_accumulation_steps,
                   help="Split each global batch into this many micro-steps "
                        "(scanned inside the jitted step); gradients "
                        "accumulate in f32 before one optimizer update.")
    p.add_argument("--weight-decay", type=float, default=d.weight_decay)
    p.add_argument("--grad-max-norm", type=float, default=d.grad_max_norm)
    p.add_argument("--optimizer-sharding", type=str,
                   default=d.optimizer_sharding, choices=["none", "zero1"],
                   help="zero1: shard AdamW moments and the weight-update "
                        "compute across the data axis (reduce-scatter grads "
                        "-> shard-local update -> allgather updates, inside "
                        "the jitted step); optimizer HBM per device drops "
                        "by the data-axis size, fp32 numerics bit-exact.")
    p.add_argument("--grad-allreduce", type=str, default=d.grad_allreduce,
                   choices=["fp32", "bf16", "int8"],
                   help="gradient-sync wire format over the data axis: "
                        "fp32 (implicit GSPMD allreduce), bf16 (cast, no "
                        "error feedback), int8 (block-scaled quantized "
                        "collective with error-feedback residuals carried "
                        "in the train state).")
    p.add_argument("--grad-quant-block", type=int, default=d.grad_quant_block,
                   help="int8 quantization block size: one f32 scale per "
                        "this many gradient elements (default 256, ~1.6%% "
                        "wire overhead).")
    p.add_argument("--grad-bucket-mb", type=float, default=d.grad_bucket_mb,
                   help="latency-hidden gradients: partition the gradient "
                        "pytree into buckets of this many MiB (reverse-"
                        "autodiff order) and issue one data-axis collective "
                        "per bucket, overlapping each bucket's wire time "
                        "with the remaining backward compute. 0 = one "
                        "tail-of-backward sync.")
    p.add_argument("--no-grad-clipping", action="store_true",
                   help="Disable gradient clipping (the reference's accidental default, train.py:272).")
    p.add_argument("--training-steps", type=int, default=d.training_steps)
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--fused-optimizer", action="store_true",
                   help="Accepted for parity; XLA always fuses the optimizer update.")
    p.add_argument("--compile", action="store_true",
                   help="Accepted for parity; the train step is always jit-compiled.")

    # model (utils.py:176-181; model shape flags are new — the reference hard-codes 8B)
    p.add_argument("--model-dtype", type=str, default=d.model_dtype)
    p.add_argument("--param-dtype", type=str, default=d.param_dtype)
    p.add_argument("--model-dim", type=int, default=d.model.dim)
    p.add_argument("--model-layers", type=int, default=d.model.n_layers)
    p.add_argument("--model-heads", type=int, default=d.model.n_heads)
    p.add_argument("--model-kv-heads", type=int, default=d.model.n_kv_heads)
    p.add_argument("--model-loop-steps", type=int, default=d.model.loop_steps,
                   help="Run the layer stack this many times over the SAME "
                        "weights, the final norm closing every pass (a "
                        "looped model; 1 = the plain decoder).")
    p.add_argument("--model-post-norms", action="store_true",
                   help="Sandwich norms: a second RMSNorm after each "
                        "sublayer, inside the residual branch.")
    p.add_argument("--model-exit-gate", action="store_true",
                   help="With --model-loop-steps >= 2: an exit gate read "
                        "from every pass; the loss is the expected "
                        "cross-entropy over the exits less "
                        "--model-exit-beta x the exit entropy.")
    p.add_argument("--model-exit-beta", type=float, default=d.model.exit_beta)
    p.add_argument("--model-attn-period", type=int,
                   default=d.model.attn_layer_period,
                   help="A hybrid stack: layer i is an attention layer where "
                        "i %% period == --model-attn-offset and a Mamba-1 "
                        "layer otherwise (Jamba's attn_layer_period; 1 = "
                        "every layer attention; d_state 16, dt_rank dim/16, "
                        "kernel 4, expansion 2 as published). Refused with "
                        "--pp > 1, --pack-sequences, --moe-experts and the "
                        "looped flags.")
    p.add_argument("--model-attn-offset", type=int,
                   default=d.model.attn_layer_offset)
    p.add_argument("--model-no-rope", action="store_true",
                   help="No rotary positions on q and k (a hybrid stack "
                        "takes its positions from the recurrence).")
    p.add_argument("--model-tie-embeddings", action="store_true",
                   help="The head reads the embedding table (no output leaf).")
    p.add_argument("--vocab-size", type=int, default=d.model.vocab_size,
                   help="Used with synthetic data; with a tokenizer, its vocab size wins.")
    p.add_argument("--use_flash_attention", "--use-flash-attention",
                   dest="use_flash_attention", action="store_true")
    p.add_argument("--attention-impl", type=str, default=d.attention_impl,
                   choices=["auto", "sdpa", "flash", "ring"],
                   help="auto: ring when --sp > 1 (sequence-parallel ring "
                        "attention), else flash if --use_flash_attention, "
                        "else sdpa.")
    p.add_argument("--moe-experts", type=int, default=d.model.n_experts,
                   help="number of MoE experts per FFN; 0 = dense (reference)")
    p.add_argument("--moe-top-k", type=int, default=d.model.moe_top_k)
    p.add_argument("--moe-capacity-factor", type=float,
                   default=d.model.moe_capacity_factor)
    p.add_argument("--moe-aux-weight", type=float,
                   default=d.model.moe_aux_weight,
                   help="load-balance aux loss scale")
    p.add_argument("--remat", action="store_true",
                   help="Rematerialize transformer blocks: the backward "
                        "sweep recomputes each block from its carry, less "
                        "what --remat-policy keeps. Without it nothing is "
                        "rematerialized, whatever the policy.")
    p.add_argument("--remat-policy", type=str, default="auto",
                   choices=["auto", "save-attn", "full"],
                   help="With --remat, what the layer scan keeps for the "
                        "backward sweep. 'auto': the richest save-set of "
                        "utils/remat.py's ladder (flash residuals, q/k/v, "
                        "the post-attention residual, the SwiGLU products) "
                        "whose modelled bytes fit what the compiler allows "
                        "on the live device kind; on a kind with no known "
                        "limit (the CPU) it keeps nothing. 'save-attn': the "
                        "flash call's residuals (the forward kernel runs "
                        "once). 'full': nothing, every block recomputed.")
    p.add_argument("--loss-chunk-size", type=int, default=0,
                   help=">0: compute the CE loss in sequence chunks of this size, "
                        "fusing the vocab projection (HBM saver for big vocabs).")

    # parallelism (new; the reference's --distributed has no shape control)
    p.add_argument("--distributed", action="store_true",
                   help="Require multi-host rendezvous; hard-fail if the "
                        "cluster env is absent or unreachable "
                        "(reference dist_utils.py:64-65).")
    p.add_argument("--dp", type=int, default=d.mesh.data, help="data-parallel axis size; -1 = all remaining")
    p.add_argument("--fsdp", type=int, default=d.mesh.fsdp)
    p.add_argument("--tp", type=int, default=d.mesh.tensor)
    p.add_argument("--sp", type=int, default=d.mesh.sequence)
    p.add_argument("--pp", type=int, default=d.mesh.pipeline,
                   help="pipeline-parallel stages (layers sharded across stages)")
    p.add_argument("--pp-microbatches", type=int, default=d.pp_microbatches,
                   help="pipeline microbatch count; 0 = number of stages")
    p.add_argument("--pp-schedule", type=str, default=d.pp_schedule,
                   choices=["gpipe", "1f1b"],
                   help="pipeline training schedule: gpipe (AD backward "
                        "wave, the default) or 1f1b (interleaved backward; "
                        "in-flight activations bounded to the stage count)")
    p.add_argument("--pp-virtual-stages", type=int, default=d.pp_virtual_stages,
                   help="interleaved 1F1B: virtual layer chunks per "
                        "physical stage (V>1 cuts the pipeline bubble to "
                        "(S-1)/(V*M+S-1); requires --pp-schedule 1f1b and "
                        "microbatches divisible by the stage count)")
    p.add_argument("--ep", type=int, default=d.mesh.expert,
                   help="expert-parallel axis size (MoE experts sharded)")

    # checkpointing (utils.py:190-232)
    p.add_argument("--checkpoint-dir", type=str, default=d.checkpoint_dir)
    p.add_argument("--checkpoint-frequency", type=_checkpoint_frequency_arg,
                   default=d.checkpoint_frequency,
                   help="save every k steps (< 1 disables), or 'auto': the "
                        "goodput autopilot adapts the interval online to "
                        "the Young-Daly optimum computed from the measured "
                        "per-save blocking cost and the interruption rate "
                        "in the failure-history sidecar (bounded by "
                        "--ckpt-auto-floor/--ckpt-auto-ceiling; decisions "
                        "emitted as ckpt_policy telemetry).")
    p.add_argument("--ckpt-auto-floor", type=int, default=d.ckpt_auto_floor,
                   help="autopilot: hard minimum save interval in steps.")
    p.add_argument("--ckpt-auto-ceiling", type=int,
                   default=d.ckpt_auto_ceiling,
                   help="autopilot: hard maximum save interval in steps "
                        "(also the bounded-prior cadence while no "
                        "interruption has been observed).")
    p.add_argument("--ckpt-auto-mtti-prior", type=float,
                   dest="ckpt_auto_mtti_prior_s",
                   default=d.ckpt_auto_mtti_prior_s,
                   help="autopilot: assumed MTTI (seconds) while zero "
                        "interruptions have been observed.")
    p.add_argument("--ckpt-auto-window", type=int,
                   default=d.ckpt_auto_window,
                   help="autopilot: number of recent interruptions in the "
                        "windowed MTTI estimate (a mid-run failure-rate "
                        "shift is tracked within this many failures).")
    p.add_argument("--resume-from-checkpoint", type=str, default=None)
    p.add_argument("--experiment_name", "--experiment-name", dest="experiment_name",
                   type=str, default=d.experiment_name)
    p.add_argument("--verify-checkpoints", action="store_true")
    p.add_argument("--max-kept-checkpoints", type=int, default=d.max_kept_checkpoints)
    p.add_argument("--checkpoint-engine", type=str,
                   default=d.checkpoint_engine, choices=ENGINES,
                   help="Checkpoint engine: vanilla single-file, sharded "
                        "(Orbax, multi-host), or zerostall (async snapshot "
                        "pipeline + content-addressed chunk dedup + in-RAM "
                        "emergency restore tier; the save window is "
                        "invisible to the train loop).")
    p.add_argument("--no-async-checkpoint", action="store_true")
    p.add_argument("--elastic-resume", type=str, default=d.elastic_resume,
                   choices=["auto", "on", "off"],
                   help="Restore a checkpoint saved on a DIFFERENT topology "
                        "onto the live mesh (reshard at restore time, after "
                        "a shardcheck preflight proves the plan feasible and "
                        "fits HBM). auto: reshard when the topology differs; "
                        "on: always run the elastic gate; off: raise a typed "
                        "TopologyMismatchError on any topology drift.")

    # time-aware (utils.py:233-248)
    p.add_argument("--timeaware-checkpointing", action="store_true")
    p.add_argument("--default-iter-time", type=float, default=d.default_iter_time)
    p.add_argument("--default-ckpt-time", type=float, default=d.default_ckpt_time)
    p.add_argument("--job-end-time", type=float, default=None,
                   help="Unix seconds; default from $JOB_END_TIME or $SLURM_JOB_END_TIME.")
    p.add_argument("--preempt-check-interval", type=int,
                   default=d.preempt_check_interval,
                   help="Run the deadline/notice check (device sync + cross-"
                        "host broadcast) every k-th step instead of every step.")

    # evaluation (beyond-parity)
    p.add_argument("--eval-frequency", type=int, default=d.eval_frequency,
                   help="Evaluate on a held-out split every k steps (0 = off).")
    p.add_argument("--eval-samples", type=int, default=d.eval_samples)
    p.add_argument("--eval-dataset", type=str, default=d.eval_dataset,
                   help="Parquet file for eval; default holds out a "
                        "synthetic split (different seed from training).")

    # observability (utils.py:152-170, 249-254)
    p.add_argument("--logging-frequency", type=int, default=d.logging_frequency)
    p.add_argument("--log-loss-to-csv", action="store_true")
    p.add_argument("--telemetry", action="store_true",
                   help="Emit a structured JSONL event stream (step timing, "
                        "checkpoint lifecycle, preemption, goodput summary); "
                        "read it with tools/summarize_telemetry.py.")
    p.add_argument("--telemetry-path", type=str, default=d.telemetry_path,
                   help="Telemetry JSONL path; default "
                        "<checkpoint-dir>/<experiment>/<experiment>_telemetry.jsonl.")
    p.add_argument("--telemetry-stdout", action="store_true",
                   help="Also mirror telemetry events into the host-0 log.")
    p.add_argument("--metrics-flush-interval", type=float,
                   dest="metrics_flush_interval_s",
                   default=d.metrics_flush_interval_s,
                   help="Seconds between metrics_snapshot telemetry events "
                        "(step-time/loader/ckpt-phase percentiles).")
    p.add_argument("--hang-watchdog-timeout", type=float,
                   dest="hang_watchdog_timeout",
                   default=d.hang_watchdog_timeout,
                   help="Seconds of no progress (train loop, loader, "
                        "checkpoint writer) before the run-health watchdog "
                        "emits hang_detected and writes a postmortem "
                        "bundle (never kills the run). 0 disables.")
    p.add_argument("--transfer-guard", type=str, default=d.transfer_guard,
                   choices=["off", "log", "disallow"],
                   help="Implicit host-transfer detection: log (stderr via "
                        "jax.transfer_guard) or disallow (implicit_transfer "
                        "telemetry event + typed error on violation).")
    p.add_argument("--profile", action="store_true")
    p.add_argument("--profile-step-start", type=int, default=d.profile_step_start)
    p.add_argument("--profile-step-end", type=int, default=d.profile_step_end)
    p.add_argument("--profile-dir", type=str, default=d.profile_dir,
                   help="where --profile writes its trace; a relative path "
                        "lies under the experiment directory")
    return p


def get_args(argv=None):
    """Parse CLI args into a TrainConfig (reference `get_args`, utils.py:105)."""
    ns = build_parser().parse_args(argv)
    model = ModelConfig(
        dim=ns.model_dim,
        n_layers=ns.model_layers,
        n_heads=ns.model_heads,
        n_kv_heads=ns.model_kv_heads,
        vocab_size=ns.vocab_size,
        n_experts=ns.moe_experts,
        moe_top_k=ns.moe_top_k,
        moe_capacity_factor=ns.moe_capacity_factor,
        moe_aux_weight=ns.moe_aux_weight,
        remat_policy=ns.remat_policy,
        loop_steps=ns.model_loop_steps,
        post_norms=ns.model_post_norms,
        exit_gate=ns.model_exit_gate,
        exit_beta=ns.model_exit_beta,
        attn_layer_period=ns.model_attn_period,
        attn_layer_offset=ns.model_attn_offset,
        rope=not ns.model_no_rope,
        tie_embeddings=ns.model_tie_embeddings,
    )
    return TrainConfig(
        dataset=ns.dataset,
        tokenizer_name_or_path=ns.tokenizer_name_or_path,
        pack_sequences=ns.pack_sequences,
        loader_stall_timeout=ns.loader_stall_timeout,
        sequence_length=ns.sequence_length,
        batch_size=ns.batch_size,
        training_samples=ns.training_samples,
        learning_rate=ns.learning_rate,
        lr_warmup_steps=ns.lr_warmup_steps,
        lr_schedule=ns.lr_schedule,
        lr_min_ratio=ns.lr_min_ratio,
        grad_accumulation_steps=ns.grad_accumulation_steps,
        weight_decay=ns.weight_decay,
        grad_max_norm=ns.grad_max_norm,
        optimizer_sharding=ns.optimizer_sharding,
        grad_allreduce=ns.grad_allreduce,
        grad_quant_block=ns.grad_quant_block,
        grad_bucket_mb=ns.grad_bucket_mb,
        grad_clipping=not ns.no_grad_clipping,
        training_steps=ns.training_steps,
        seed=ns.seed,
        model=model,
        model_dtype=ns.model_dtype,
        param_dtype=ns.param_dtype,
        use_flash_attention=ns.use_flash_attention,
        attention_impl=ns.attention_impl,
        remat=ns.remat,
        loss_chunk_size=ns.loss_chunk_size,
        mesh=MeshConfig(data=ns.dp, fsdp=ns.fsdp, tensor=ns.tp, sequence=ns.sp,
                        pipeline=ns.pp, expert=ns.ep),
        pp_microbatches=ns.pp_microbatches,
        pp_schedule=ns.pp_schedule,
        pp_virtual_stages=ns.pp_virtual_stages,
        distributed=ns.distributed,
        checkpoint_dir=ns.checkpoint_dir,
        # "auto" keeps the numeric default as the static-counterfactual
        # baseline (and the autopilot's rate-limit starting point)
        checkpoint_frequency=(
            TrainConfig.checkpoint_frequency
            if ns.checkpoint_frequency == "auto"
            else ns.checkpoint_frequency
        ),
        checkpoint_auto=ns.checkpoint_frequency == "auto",
        ckpt_auto_floor=ns.ckpt_auto_floor,
        ckpt_auto_ceiling=ns.ckpt_auto_ceiling,
        ckpt_auto_mtti_prior_s=ns.ckpt_auto_mtti_prior_s,
        ckpt_auto_window=ns.ckpt_auto_window,
        resume_from_checkpoint=ns.resume_from_checkpoint,
        experiment_name=ns.experiment_name,
        verify_checkpoints=ns.verify_checkpoints,
        max_kept_checkpoints=ns.max_kept_checkpoints,
        checkpoint_engine=ns.checkpoint_engine,
        async_checkpoint=not ns.no_async_checkpoint,
        elastic_resume=ns.elastic_resume,
        timeaware_checkpointing=ns.timeaware_checkpointing,
        default_iter_time=ns.default_iter_time,
        default_ckpt_time=ns.default_ckpt_time,
        job_end_time=ns.job_end_time,
        preempt_check_interval=ns.preempt_check_interval,
        eval_frequency=ns.eval_frequency,
        eval_samples=ns.eval_samples,
        eval_dataset=ns.eval_dataset,
        logging_frequency=ns.logging_frequency,
        log_loss_to_csv=ns.log_loss_to_csv,
        telemetry=ns.telemetry,
        telemetry_path=ns.telemetry_path,
        telemetry_stdout=ns.telemetry_stdout,
        metrics_flush_interval_s=ns.metrics_flush_interval_s,
        hang_watchdog_timeout=ns.hang_watchdog_timeout,
        transfer_guard=ns.transfer_guard,
        profile=ns.profile,
        profile_step_start=ns.profile_step_start,
        profile_step_end=ns.profile_step_end,
        profile_dir=ns.profile_dir,
    )
