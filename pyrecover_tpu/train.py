"""End-to-end training driver.

The reference's `train.py:37-400` re-expressed functionally: all mutable
training state lives in one pytree (TrainState) threaded through a jitted
step; DDP/NCCL init is replaced by mesh construction + sharding; checkpoint
strategy dispatch, periodic + time-aware + final saves, resume, metrics, and
profiling windows keep 1:1 capability parity (call-stack map in SURVEY §3.1).

Run:  python -m pyrecover_tpu.train --training-steps 100 ...
"""

import contextlib
import dataclasses
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from pyrecover_tpu import telemetry
from pyrecover_tpu.telemetry import detectors
from pyrecover_tpu.checkpoint.registry import checkpoint_path, list_checkpoints
from pyrecover_tpu.checkpoint.engine import loaded_engine_modules, open_engine
from pyrecover_tpu.config import TrainConfig, get_args
from pyrecover_tpu.data import DataLoader, StatefulSampler, SyntheticTextDataset
from pyrecover_tpu.metrics import LossCSVLogger, ThroughputMeter, WallTimeTotals
from pyrecover_tpu.ops import selective_scan
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.parallel.mesh import create_mesh, initialize_distributed
from pyrecover_tpu.parallel.sharding import _leaf_rule
from pyrecover_tpu.preempt import (
    PreemptionWatcher,
    read_requeue_marker,
    write_requeue_marker,
)
from pyrecover_tpu.resilience import faults, quarantine_checkpoint
from pyrecover_tpu.train_state import (
    create_train_state,
    exit_stats_fields,
    make_eval_step,
    make_train_step,
)
from pyrecover_tpu.utils.logging import init_logger, log_host0
from pyrecover_tpu.utils.perf import get_num_params

# upper bound on how long train()'s unwind waits for an in-flight
# background checkpoint writer before declaring it wedged (TimeoutError →
# logged on an already-failing unwind, raised otherwise). Generous: a
# healthy writer finishes in seconds; only a dead disk reaches this.
_BG_JOIN_TIMEOUT_S = 600.0


def state_pspecs(abstract_state, optimizer_sharding="none", mesh_shape=None):
    """PartitionSpecs for the FULL train state. Optimizer moments mirror the
    params pytree (same leaf names), so the same path rules shard them
    identically; anything unmatched (counters, RNG) is replicated.

    ``optimizer_sharding="zero1"`` (with a ``mesh_shape`` dict for the
    divisibility decisions) additionally shards every ``.opt_state``
    moment over the data axis (parallel/sharding.py:zero1_leaf_spec) —
    the ZeRO-1 layout the decomposed update in make_train_step computes
    against. The error-feedback residual (``.grad_residual``, present
    only under int8 gradient collectives) always carries its per-replica
    leading dim on the data axis."""
    from pyrecover_tpu.parallel.sharding import (
        grad_residual_spec,
        zero1_leaf_spec,
    )

    def spec_for(path, leaf):
        root = str(getattr(path[0], "name", "")) if path else ""
        if root == "grad_residual":
            return grad_residual_spec(leaf.ndim)
        rule = _leaf_rule(path)
        if rule is None or len(rule) != leaf.ndim:
            rule = P(*([None] * leaf.ndim))
        if (
            optimizer_sharding == "zero1"
            and mesh_shape
            and root == "opt_state"
        ):
            return zero1_leaf_spec(rule, leaf.shape, mesh_shape)
        return rule

    return jax.tree_util.tree_map_with_path(spec_for, abstract_state)


def init_sharded_state(rng, model_config, optimizer, mesh,
                       optimizer_sharding="none", grad_allreduce="fp32",
                       grad_quant_block=None):
    """Initialize the train state directly INTO its shardings: params are
    compiled to materialize shard-local (no host-memory or single-device
    staging), which is what makes >HBM-sized models initializable."""
    mesh_shape = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    residual_replicas = (
        mesh_shape.get("data", 1) if grad_allreduce == "int8" else 0
    )

    def init_fn(key):
        return create_train_state(
            key, model_config, optimizer,
            grad_residual_replicas=residual_replicas,
            grad_quant_block=grad_quant_block,
        )

    abstract = jax.eval_shape(init_fn, rng)
    specs = state_pspecs(abstract, optimizer_sharding, mesh_shape)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    with jax.sharding.set_mesh(mesh):
        return jax.jit(init_fn, out_shardings=shardings)(rng)


def build_dataset(config):
    if config.dataset:
        from pyrecover_tpu.data.parquet import ParquetTextDataset, load_tokenizer

        tokenizer = load_tokenizer(config.tokenizer_name_or_path)
        if config.pack_sequences:
            from pyrecover_tpu.data.packed import PackedParquetTextDataset

            ds = PackedParquetTextDataset(
                config.dataset,
                tokenizer,
                config.sequence_length,
                training_samples=config.training_samples,
            )
        else:
            ds = ParquetTextDataset(
                config.dataset,
                tokenizer,
                config.sequence_length,
                training_samples=config.training_samples,
            )
        vocab_size = max(len(tokenizer), config.model.vocab_size)
        model = dataclasses.replace(config.model, vocab_size=vocab_size)
        return ds, ds.pad_token_id, model
    if config.pack_sequences:
        log_host0(
            "--pack-sequences has no effect with synthetic data "
            "(synthetic rows are already dense); continuing unpacked"
        )
    # synthetic path: deterministic, tokenizer-free
    n = config.training_samples or max(
        config.batch_size * config.training_steps, config.batch_size
    )
    ds = SyntheticTextDataset(
        num_samples=n,
        seq_len=config.sequence_length,
        vocab_size=config.model.vocab_size,
        seed=config.seed,
    )
    return ds, 0, config.model


class _PadFilledView:
    """Dataset view of ``n_real`` corpus rows, length-padded to a whole
    number of batches with all-pad rows (zero loss contribution)."""

    def __init__(self, ds, n_real, n_total, pad_token_id, seq_len):
        self._ds = ds
        self._n_real = int(n_real)
        self._n_total = int(n_total)
        self._pad_row = np.full((int(seq_len) + 1,), pad_token_id, np.int32)

    def __len__(self):
        return self._n_total

    def __getitem__(self, idx):
        idx = int(idx)
        return self._ds[idx] if idx < self._n_real else self._pad_row


def build_eval_runner(config, model_config, pad_token_id, mesh):
    """Held-out evaluation: returns ``run_eval(state) -> mean_loss`` or None.

    Beyond-parity — the reference has no eval loop. ``--eval-dataset``
    names a parquet file; without it a synthetic split on a DIFFERENT seed
    from training serves as the held-out data. Losses are averaged exactly
    (Σ CE-sums / Σ valid tokens) across ``--eval-samples`` samples.
    """
    if config.eval_frequency <= 0:
        return None
    # keep the TRAINING batch size: it is already divisible by the mesh's
    # batch shards; the sample count is rounded up to whole batches
    batch = config.batch_size
    if config.eval_dataset:
        from pyrecover_tpu.data.parquet import ParquetTextDataset, load_tokenizer

        tokenizer = load_tokenizer(config.tokenizer_name_or_path)
        corpus = ParquetTextDataset(
            config.eval_dataset, tokenizer, config.sequence_length,
            training_samples=0,  # natural length; no wraparound
        )
        # the eval tokenizer's own pad id, not the training dataset's —
        # wrong masking would score pad positions as real tokens
        pad_token_id = corpus.pad_token_id
        # 0 = the whole corpus (the training_samples convention)
        n_requested = min(config.eval_samples or len(corpus), len(corpus))
        n_batches = max((n_requested + batch - 1) // batch, 1)
        # fill the final batch with ALL-PAD rows: their labels collate to
        # IGNORE_INDEX, contributing exactly zero to Σ CE and Σ tokens —
        # no document is double-counted (wraparound would reweight the
        # corpus head)
        eval_ds = _PadFilledView(
            corpus, n_requested, n_batches * batch, pad_token_id,
            config.sequence_length,
        )
    else:
        # Same distribution, different draw. The synthetic task's sequence
        # universe is closed (affine recurrence keyed by start token), so
        # this measures fit on the distribution, not generalization to
        # unseen text — use --eval-dataset for a genuinely held-out corpus.
        n_requested = config.eval_samples or 64
        n_batches = max((n_requested + batch - 1) // batch, 1)
        eval_ds = SyntheticTextDataset(
            num_samples=n_batches * batch,
            seq_len=config.sequence_length,
            vocab_size=model_config.vocab_size,
            seed=config.seed + 1,
        )
    eval_step = make_eval_step(model_config, config.loss_chunk_size)

    # ONE prefetching loader lives across eval calls (constructing a cold
    # loader per call stalled the device through host-side tokenize/collate
    # between batches — round-3 verdict weak #7). The eval view's length is
    # exactly n_batches×batch and the sampler is sequential, so consuming
    # n_batches batches per call cycles back to the start: every eval sees
    # the identical full eval set, and the background prefetch keeps the
    # next batch ready while the device runs the current one.
    sampler = StatefulSampler(
        dataset_len=len(eval_ds), global_batch_size=batch,
        seed=config.seed + 1, shuffle=False,
    )
    loader = DataLoader(
        eval_ds, sampler, pad_token_id=pad_token_id, mesh=mesh,
        prefetch=2, num_workers=2,
        stall_timeout=config.loader_stall_timeout,
    )

    def run_eval(state):  # jaxlint: hot-loop
        loader.start()  # idempotent; lazy so no thread spins if eval never runs
        ce_sum = n_tok = None
        for _ in range(n_batches):
            _, b = next(loader)
            s, n = eval_step(state.params, b)
            # accumulate ON DEVICE: no per-batch host sync
            ce_sum = s if ce_sum is None else ce_sum + s
            n_tok = n if n_tok is None else n_tok + n
        return float(ce_sum) / max(int(n_tok), 1)  # one sync per eval

    run_eval.loader = loader  # train() stops it at exit
    return run_eval


def _resume(config, exp_dir, state, sampler, engine, totals):  # jaxlint: sync-point
    """Resume from ``config.resume_from_checkpoint`` (reference
    train.py:195-212). Returns ``(start_step, state)``.

    "latest" walks candidates newest→oldest and FALLS BACK past a
    corrupt/truncated/torn checkpoint — exactly what a crash during or
    after the newest save leaves behind, on EVERY engine; the integrity
    pre-check catches it and the fallback turns it into a recovery
    instead of a dead job. ``engine`` is the run's checkpoint engine
    (``checkpoint/engine.py``); None opens one from ``config``.
    Multi-host safety: corruption is judged by a host-LOCAL pre-check on
    host 0 and the verdict broadcast, so every host enters the collective
    load for the SAME candidate (a per-host exception inside the load
    would desynchronize the barrier protocol). A structural mismatch
    (CheckpointStructureError: wrong leaf count/shapes = wrong model
    config) fails hard — every candidate would fail identically and a
    silent fresh start would let retention pruning destroy the intact
    checkpoints it skipped. An explicitly named checkpoint also fails
    hard: the user asked for THAT file.

    Topology-elastic resume (checkpoint/elastic.py): BEFORE any restore
    I/O, host 0 diffs the candidate's saved topology (a header read)
    against the live mesh. When they differ and ``--elastic-resume`` is
    not off, a mandatory shardcheck preflight proves the reshard plan is
    expressible (SC11) and fits the target HBM budget (SC05); a failed
    preflight FALLS BACK to the newest checkpoint that does fit — without
    quarantining, the checkpoint is intact, it just doesn't fit this
    mesh. With ``--elastic-resume off`` a topology drift raises a typed
    ``TopologyMismatchError`` naming both topologies.

    An engine with a RAM tier (``engine.ram_tier``; zerostall's
    ``checkpoint/zerostall/emergency.py``): the tier is consulted FIRST on
    a "latest" resume. When host 0 holds a committed snapshot that is at
    least as fresh as the newest disk manifest, on the SAME topology,
    and its recomputed chunk digests match the committed manifest, the
    restore happens from RAM in milliseconds — the disk tier (possibly
    behind, mid-write, or gone) is never touched. Any gate failure
    falls through to the normal disk walk silently; a record that
    passes the gate but fails mid-restore falls back loudly
    (``emergency_restore_rejected``) single-process, and RAISES on a
    pod — the broadcast verdict already committed every host to the RAM
    path, so one host privately rejoining the disk walk would leave its
    verdict collectives one participant short (deadlock).
    """
    from pyrecover_tpu.checkpoint import elastic
    from pyrecover_tpu.checkpoint.elastic import TopologyMismatchError
    from pyrecover_tpu.checkpoint.engine import CheckpointStructureError
    from pyrecover_tpu.checkpoint.registry import parse_step
    from pyrecover_tpu.parallel.mesh import (
        broadcast_host0_obj,
        broadcast_host0_scalar,
        state_topology,
    )

    if engine is None:
        with open_engine(config) as engine:
            return _resume(config, exp_dir, state, sampler, engine, totals)
    t0 = time.monotonic()
    target = config.resume_from_checkpoint
    explicit = target != "latest"
    # None or not on EVERY host alike, whatever record each holds
    tier = None if explicit else engine.ram_tier(exp_dir)
    if explicit:
        candidates = [target]
    else:
        # every host must walk the SAME candidate list: the per-candidate
        # verdict broadcasts below are positional, so transiently
        # divergent per-host directory listings (host 0 mid-quarantine,
        # shared-FS stragglers) would have hosts exchanging verdicts
        # about DIFFERENT checkpoints. Host 0's listing is authoritative.
        candidates = broadcast_host0_obj(
            [str(p) for p in
             list_checkpoints(exp_dir, engine=engine.name)[::-1]]
        )
        if not candidates:
            # the "anything at all to restore?" decision must also be
            # congruent: only host 0 ever holds an emergency record, so a
            # per-host peek here would send host 0 into the use_ram
            # broadcast below while every peer had already returned fresh
            have_ram = 0
            if tier is not None:
                if jax.process_index() == 0:
                    have_ram = int(tier.peek() is not None)
                have_ram = int(broadcast_host0_scalar(have_ram))
            if not have_ram:
                log_host0(
                    "No checkpoint found in %s; starting fresh", exp_dir
                )
                return 0, state

    # ---- in-RAM emergency tier ---------------------------------------------
    # host-0 gate: fresh enough (>= newest disk manifest), same topology,
    # digests intact; verdict broadcast so every host takes the same path
    if tier is not None:
        use_ram = 0
        if jax.process_index() == 0:
            best_disk = parse_step(candidates[0]) if candidates else -1
            record = tier.usable(
                state_topology(state), min_step=max(best_disk, 0)
            )
            if record is not None:
                ok, reason = tier.verify(record)
                if ok:
                    use_ram = 1
                else:
                    telemetry.emit(
                        "emergency_restore_rejected", reason=reason,
                        step=record["step"],
                    )
                    log_host0(
                        "in-RAM emergency record rejected (%s); using the "
                        "disk tier", reason, level=30,  # WARNING
                    )
        if int(broadcast_host0_scalar(use_ram)) == 1:
            try:
                state, sampler_meta, doc = tier.restore(state)
            except Exception as e:
                # verified on host 0 a moment ago — reaching here means a
                # race/rot between gate and restore; disk is the truth
                telemetry.emit(
                    "emergency_restore_rejected",
                    reason=f"{type(e).__name__}: {e}",
                )
                if jax.process_count() > 1:
                    # the use_ram verdict already committed EVERY host to
                    # the RAM path; one host silently falling through to
                    # the disk walk (and its per-candidate verdict
                    # broadcasts) while the others return resumed would
                    # leave those collectives one participant short
                    # forever. A pod fails loudly here — same discipline
                    # as the disk-path restore handler below.
                    raise
                log_host0(
                    "emergency-tier restore failed (%s: %s); falling back "
                    "to the disk tier", type(e).__name__, e, level=30,
                )
            else:
                start_step = int(doc.get("step", 0))
                sampler.seek(sampler_meta.get("consumed", start_step))
                totals.ckpt_load_s += time.monotonic() - t0
                log_host0(
                    "Resumed from the in-RAM emergency tier at step %d "
                    "(%.3f s)", start_step, totals.ckpt_load_s,
                )
                telemetry.emit(
                    "resume", path="<emergency-ram>", step=start_step,
                    seconds=round(totals.ckpt_load_s, 4),
                )
                return start_step, state
        if not candidates:
            log_host0("No checkpoint found in %s; starting fresh", exp_dir)
            return 0, state
    rejected_preflight = []
    for cand in candidates:
        prechecked = False
        plan = None
        # host-0 verdict, agreed everywhere, BEFORE any collective:
        # 1 = ok, 0 = corrupt (fall back), 2 = structure mismatch
        # (wrong model config — fatal on EVERY candidate, raised on
        # all hosts so nobody is left waiting in a collective),
        # 3 = elastic preflight infeasible (fall back, NO quarantine),
        # 4 = topology mismatch with --elastic-resume off (fatal),
        # 5 = ok with the elastic reshard path active
        verdict, reason = 1, ""
        if jax.process_index() == 0:
            try:
                gate, reason, plan = elastic.resume_gate(
                    config.elastic_resume, cand, state
                )
                verdict = {
                    elastic.GATE_OK: 1,
                    elastic.GATE_ELASTIC: 5,
                    elastic.GATE_INFEASIBLE: 3,
                    elastic.GATE_MISMATCH: 4,
                }[gate]
                if verdict in (1, 5) and not explicit:
                    ok, why = engine.precheck(cand, state)
                    if not ok:
                        verdict, reason = 0, why
            # faultcheck: disable-next=recovery-swallow -- not a swallow:
            # the handler folds the failure into the host-0 verdict that
            # is broadcast and re-raised on EVERY host a few lines down
            # (raising here directly would desynchronize the collective)
            except CheckpointStructureError as e:
                verdict, reason = 2, str(e)
        verdict = int(broadcast_host0_scalar(verdict))
        if verdict == 2:
            raise CheckpointStructureError(
                f"checkpoint {cand} does not fit the configured "
                f"model{': ' + reason if reason else ''}"
            )
        if verdict == 4:
            # loud + diagnosable: the typed error names both topologies
            # (the doctor reads the event as a mesh_mismatch)
            telemetry.emit(
                "topology_mismatch", path=str(cand), reason=reason,
                elastic_resume=config.elastic_resume,
            )
            raise TopologyMismatchError(path=cand, message=(
                reason or f"checkpoint {cand} was saved on a different "
                "topology than the live mesh (--elastic-resume off)"
            ))
        if verdict == 3:
            telemetry.emit(
                "elastic_preflight_failed", path=str(cand), reason=reason,
            )
            if explicit:
                # the user asked for THAT checkpoint; it cannot fit here
                raise TopologyMismatchError(path=cand, detail=reason or (
                    "elastic preflight rejected the reshard plan"
                ))
            log_host0(
                "Checkpoint %s cannot be resharded onto this mesh (%s); "
                "falling back to the previous one", cand, reason,
                level=30,  # WARNING
            )
            # NOT quarantined: the checkpoint is intact and will fit
            # again when matching capacity returns
            rejected_preflight.append(cand)
            continue
        if verdict == 0:
            log_host0(
                "Checkpoint %s failed integrity pre-check (%s); "
                "falling back to the previous one", cand, reason,
                level=30,  # WARNING
            )
            telemetry.emit(
                "ckpt_precheck_failed", path=str(cand), reason=reason
            )
            # move the corpse into .corrupt/ (host 0; atomic rename):
            # the next restart must not re-discover and re-skip it,
            # and retention must never count it against max_keep. The
            # fallback verdict was already broadcast, so every host
            # agrees this candidate is dead before the move happens.
            if jax.process_index() == 0:
                quarantine_checkpoint(cand, reason=reason)
            continue
        prechecked = not explicit
        elastic_active = verdict == 5
        reshard_span = (
            telemetry.span(
                "reshard", path=str(cand), metric="reshard_s",
            ) if elastic_active else contextlib.nullcontext()
        )
        try:
            with reshard_span:
                state, sampler_meta, meta = engine.load(
                    cand, state, prechecked=prechecked
                )
        except Exception as e:
            if (
                explicit
                or isinstance(e, CheckpointStructureError)
                or jax.process_count() > 1
            ):
                # explicit request, wrong-model-config, or a pod (where a
                # mid-load divergence cannot be recovered safely —
                # corruption the precheck can see never reaches here on a
                # pod; only tensor-data-level damage does)
                raise
            log_host0(
                "Checkpoint %s failed to restore (%s: %s); falling back "
                "to the previous one", cand, type(e).__name__, e,
                level=30,  # WARNING
            )
            telemetry.emit(
                "ckpt_restore_fallback", path=str(cand),
                reason=f"{type(e).__name__}: {e}",
            )
            # tensor-data damage the cheap precheck couldn't see: same
            # quarantine protocol (single-process only reaches here)
            quarantine_checkpoint(
                cand, reason=f"{type(e).__name__}: {e}"
            )
            continue
        start_step = int(meta.get("step", int(np.asarray(state.step))))
        if elastic_active:
            # the reshard happened: account for it in the event stream.
            # Plan accounting exists on host 0 (where the gate ran); the
            # whole block — including the sampler-rescale validation
            # round-trip, whose result is advisory — is host-0-local
            # telemetry with no collectives, so it nests entirely under
            # the rank gate instead of leaking the unbroadcast
            # ``live_replicas`` into all-host control flow (distcheck
            # DC03). The actual data-pipeline rescale needs no per-host
            # work at all: the sampler's order is a pure function of
            # (seed, epoch, cursor), so the global ``seek`` below
            # re-derives every replica's split exactly — proven by the
            # merge/split round-trip (preflight established feasibility).
            if jax.process_index() == 0 and plan is not None:
                telemetry.emit(
                    "elastic_resume", path=str(cand), step=start_step,
                    saved_topology=plan.saved_topology,
                    target_topology=plan.target_topology,
                    resharded_leaves=plan.resharded_leaves,
                    plan_bytes_moved=plan.bytes_moved,
                )
                saved_replicas = int(sampler_meta.get("replicas", 0) or 0)
                tgt_mesh = plan.target_topology.get("mesh") or {}
                live_replicas = int(tgt_mesh.get("data", 1)) * int(
                    tgt_mesh.get("fsdp", 1)
                )
                if saved_replicas and live_replicas and (
                    saved_replicas != live_replicas
                ):
                    from pyrecover_tpu.data.sampler import (
                        rescale_sampler_state,
                    )

                    rescale_sampler_state(
                        {k: v for k, v in sampler_meta.items()
                         if k not in ("consumed", "replicas")},
                        live_replicas,
                    )
                    telemetry.emit(
                        "sampler_rescaled", saved_replicas=saved_replicas,
                        target_replicas=live_replicas,
                        consumed=int(
                            sampler_meta.get("consumed", start_step)
                        ),
                    )
        sampler.seek(sampler_meta.get("consumed", start_step))
        totals.ckpt_load_s += time.monotonic() - t0
        log_host0(
            "Resumed from %s at step %d (%.2f s)", cand, start_step,
            totals.ckpt_load_s,
        )
        telemetry.emit(
            "resume", path=str(cand), step=start_step,
            seconds=round(totals.ckpt_load_s, 4),
        )
        return start_step, state
    # refuse to run: a fresh start would save new checkpoints and retention
    # pruning would then delete the (possibly still recoverable) old ones
    detail = ""
    if rejected_preflight:
        from pathlib import Path

        names = ", ".join(Path(p).name for p in rejected_preflight[:4])
        detail = (
            f" ({len(rejected_preflight)} rejected by the elastic "
            f"preflight for this topology: {names} — they are intact and "
            "will restore when matching capacity returns)"
        )
    raise RuntimeError(
        f"every checkpoint in {exp_dir} failed to restore{detail}; "
        "refusing to start fresh over existing checkpoints — inspect "
        "them with tools/inspect_checkpoint.py or move them aside"
    )


def train(config: TrainConfig):
    """Run training. Thin shell around ``_train_impl`` that guarantees the
    ``run_summary`` telemetry event (goodput accounting) is emitted and the
    run-owned telemetry sinks are torn down on EVERY exit path — normal
    completion, early stop, and crash (a crashed run's partial goodput
    record is exactly what the post-mortem needs)."""
    init_logger()
    # --distributed makes a failed/absent rendezvous fatal (reference
    # dist_utils.py:64-65) instead of degrading to N divergent solo runs
    initialize_distributed(required=config.distributed)
    totals = WallTimeTotals()
    t_entry = time.monotonic()
    owned_sinks = []
    status = {"status": "error", "step": 0}
    try:
        return _train_impl(config, totals, t_entry, owned_sinks, status)
    finally:
        totals.wall_s = time.monotonic() - t_entry
        # black-box dump FIRST while unwinding an error: the bundle must
        # capture the ring/open spans before teardown, and dumping here
        # (not just in sys.excepthook) means a caller catching the
        # exception around train() cannot swallow the postmortem
        exc = sys.exc_info()
        if exc[0] is not None and not issubclass(
            exc[0], (KeyboardInterrupt, SystemExit)
        ):
            telemetry.flight.dump("unhandled_exception", exc=exc)
        # final percentile snapshot first: the run_summary consumer gets
        # goodput AND the step-time/ckpt-phase distributions in one stream
        telemetry.metrics.flush(reason="run_end")
        telemetry.emit(
            "run_summary", status=status["status"], step=status["step"],
            **totals.as_dict(),
            # peak HBM vs the device budget (empty off-accelerator): the
            # silent-creep-toward-OOM detector's run-level verdict
            **detectors.hbm_run_summary(),
        )
        exporter = status.pop("exporter", None)
        if exporter is not None:
            try:
                exporter.stop()
            except Exception as e:
                # teardown must not mask the run's own exit path; a
                # wedged exporter thread is daemonic and dies with us
                log_host0(
                    "metrics exporter did not stop cleanly: %s", e,
                    level=30,  # WARNING
                )
        for sink in owned_sinks:
            telemetry.remove_sink(sink)
        telemetry.flight.uninstall()


def _train_impl(config, totals, t_entry, owned_sinks, status):

    # refuse a checkpoint "dir" that exists as a file (reference train.py:138-139)
    from pathlib import Path as _Path

    ckpt_root = _Path(config.checkpoint_dir)
    if ckpt_root.exists() and not ckpt_root.is_dir():
        raise NotADirectoryError(
            f"--checkpoint-dir {ckpt_root} exists and is not a directory"
        )

    mesh = create_mesh(config.mesh)
    log_host0(
        "Devices: %d (%s) | mesh %s | processes %d",
        jax.device_count(),
        jax.devices()[0].device_kind,
        dict(mesh.shape),
        jax.process_count(),
    )

    dataset, pad_token_id, model_config = build_dataset(config)

    # ---- what the layer scan keeps under remat (utils/remat.py) ------------
    # resolved BEFORE anything builds the model: under `auto` the richest
    # save-set of the ladder whose modelled bytes fit what the compiler
    # enforces for this device kind; `remat: false` is no remat. The
    # decision's event is emitted once the step is compiled (it carries
    # the compiler's own peak and whether a rung was refused).
    remat_decision = None
    if model_config.remat:
        from pyrecover_tpu.utils.remat import resolve_remat_policy

        remat_decision = resolve_remat_policy(
            model_config,
            {str(k): int(v) for k, v in dict(mesh.shape).items()},
            batch_size=config.batch_size, seq_len=config.sequence_length,
            loss_chunk_size=config.loss_chunk_size,
            optimizer_sharding=config.optimizer_sharding,
            grad_allreduce=config.grad_allreduce,
            quant_block=config.grad_quant_block,
            device_kind=jax.devices()[0].device_kind,
        )
        model_config = remat_decision.apply(model_config)
        log_host0(
            "remat (%s): rung %s on %s keeps %s (modelled %.2f GiB/device, "
            "compiler's limit %s; per-chip batch suggestion %d)",
            model_config.remat_policy, remat_decision.rung,
            remat_decision.device_kind or "<unknown device kind>",
            ", ".join(remat_decision.saved_names) or "nothing",
            remat_decision.table[remat_decision.rung] / 2**30,
            (f"{remat_decision.limit_bytes / 2**30:.2f} GiB"
             if remat_decision.limit_bytes else "unknown"),
            remat_decision.suggested_batch_per_chip,
        )

    sampler = StatefulSampler(
        dataset_len=len(dataset),
        global_batch_size=config.batch_size,
        seed=config.seed,
        num_samples=config.training_samples or None,
    )

    optimizer, _ = build_optimizer(config)
    rng = jax.random.key(config.seed)
    state = init_sharded_state(
        rng, model_config, optimizer, mesh,
        optimizer_sharding=config.optimizer_sharding,
        grad_allreduce=config.grad_allreduce,
        grad_quant_block=config.grad_quant_block,
    )
    n_params = get_num_params(state.params)
    log_host0("Model: %.2fM params | %s", n_params / 1e6, model_config)

    exp_dir = checkpoint_path(config.checkpoint_dir, config.experiment_name, 0).parent

    # ---- flight recorder (always on, --telemetry or not) -------------------
    # the in-memory ring + black-box dump hooks: unhandled exceptions,
    # fatal signals (faulthandler), the SIGTERM-escalation path, and the
    # hang watchdog all write a postmortem bundle under .postmortem/
    detectors.reset_hbm()
    telemetry.flight.install(exp_dir, config=dataclasses.asdict(config))

    # ---- telemetry sinks + previous attempt's progress high-water mark -----
    # prior_step: the highest step the PREVIOUS attempt completed, recovered
    # from the requeue/done marker (graceful stops) and the telemetry JSONL
    # itself (flushed per event, so it survives hard kills). Post-resume
    # steps at or below it are re-done work — the goodput accounting's
    # replayed-step ledger.
    prior_step = None
    telemetry_path = None
    resume_requested = bool(config.resume_from_checkpoint)
    if config.telemetry:
        telemetry_path = (
            _Path(config.telemetry_path) if config.telemetry_path
            else exp_dir / f"{config.experiment_name}_telemetry.jsonl"
        )
    if resume_requested:
        marker = read_requeue_marker(exp_dir)
        if marker and marker.get("step") is not None:
            prior_step = int(marker["step"])
        if telemetry_path is not None:
            recorded = telemetry.last_recorded_step(telemetry_path)
            if recorded is not None:
                prior_step = max(prior_step or 0, recorded)
    if telemetry_path is not None:
        # append across resume cycles (one continuous event stream per
        # experiment, like the loss CSV); truncate on a fresh run
        owned_sinks.append(telemetry.add_sink(
            telemetry.JsonlSink(telemetry_path, append=resume_requested)))
    if config.telemetry_stdout:
        owned_sinks.append(telemetry.add_sink(telemetry.LogSink()))
    # live-metrics endpoint ($PYRECOVER_METRICS_PORT): the per-process
    # exposition half of the live telemetry plane — started after the
    # sinks so exporter_started lands in the stream, stopped (bounded
    # join) on train()'s unwind
    from pyrecover_tpu.telemetry.exporter import maybe_start_from_env

    status["exporter"] = maybe_start_from_env()
    # obscheck: disable-next=hot-path-emit -- once per run, emitted
    # before the first loop iteration (OB05 is function-granular)
    telemetry.emit(
        "run_start",
        devices=jax.device_count(),
        device_kind=jax.devices()[0].device_kind,
        processes=jax.process_count(),
        mesh={k: int(v) for k, v in dict(mesh.shape).items()},
        params_m=round(n_params / 1e6, 3),
        # a looped model sweeps its layers loop_steps times a forward:
        # the work and the saved carries scale with layer_passes
        loop_steps=model_config.loop_steps,
        layer_passes=model_config.layer_passes,
        # a hybrid stack: layers of each kind, the floats of recurrent
        # state a token has in one Mamba layer, the scan's chunk
        attn_layers=model_config.n_attn_layers,
        mamba_layers=model_config.n_mamba_layers,
        ssm_state_elems=(
            model_config.ssm_state_elems if model_config.hybrid else 0),
        scan_chunk=(
            selective_scan.SCAN_CHUNK if model_config.hybrid else 0),
        batch_size=config.batch_size,
        sequence_length=config.sequence_length,
        grad_accum_steps=config.grad_accumulation_steps,
        training_steps=config.training_steps,
        resume=resume_requested,
        # what importing this module cost, and which checkpoint modules
        # it had loaded by then (an engine's come in at open_engine)
        import_s=IMPORT_S,
        ckpt_modules=CKPT_MODULES,
    )
    # a declared accelerator ($PYRECOVER_EXPECT_ACCELERATOR) that resolved
    # to cpu is an error: platform_fallback event, then raise
    detectors.check_expected_accelerator()

    # ---- checkpoint engine (reference train.py:153-161) --------------------
    # at most one save is in flight, and the engine serialises behind it
    engine = open_engine(config)

    def save_ckpt(step, final=False):
        path = checkpoint_path(
            config.checkpoint_dir, config.experiment_name, step,
            final=final, engine=engine.name,
        )
        # mesh-replicated GLOBAL scalar, like every other state leaf: a
        # bare jnp.asarray would be host-local, which the multi-host
        # sharded engine refuses to serialize ("Cannot serialize host
        # local jax.Array" — found by the 2-process driver test)
        epoch = jax.device_put(
            np.asarray(sampler_epoch_of(step), np.int32),
            NamedSharding(mesh, P()),
        )
        state_to_save = dataclasses.replace(state, epoch=epoch)
        # "replicas": how many ways the batch axis is sharded right now —
        # the elastic-resume preflight proves the sampler can rescale to a
        # different replica count before any restore is attempted
        mesh_shape = dict(mesh.shape)
        sampler_meta = {
            "consumed": int(step),
            "replicas": int(mesh_shape.get("data", 1))
            * int(mesh_shape.get("fsdp", 1)),
            **sampler.state_dict(),
        }
        extra = {"step": int(step), "epoch": sampler_epoch_of(step)}
        # while the save is in flight a FIRST signal defers exit until the
        # commit completes (the normal deferred-exit path); a SECOND one
        # escalates to an immediate requeue marker + exit — the scheduler
        # has stopped waiting, so must we
        if watcher is not None:
            watcher.arm_escalation(exp_dir, step)
        save_span = telemetry.spans.begin(
            "ckpt_save", step=int(step), final=bool(final),
            engine=engine.name,
        )
        try:
            secs = engine.save(
                path, state_to_save, sampler_meta, extra_meta=extra,
                final=final,
            )
        except BaseException as e:
            save_span.end(ok=False, error=f"{type(e).__name__}: {e}")
            raise
        finally:
            if watcher is not None:
                watcher.disarm_escalation()
        save_span.end()
        # the train-loop stall this save cost, under its honest name: the
        # histogram feeds metrics_snapshot percentiles (and bench), the
        # totals split blocking (lost) from shadow (overlapped) work
        totals.ckpt_blocking_s += secs
        telemetry.metrics.histogram("ckpt_blocking_s").observe(secs)
        log_host0("Saved checkpoint %s in %.2f s", path.name, secs)
        # obscheck: disable-next=hot-path-emit -- once per SAVE, not per
        # step: every save_ckpt call is interval-gated by its caller
        telemetry.emit(
            "ckpt_saved", step=int(step), path=path.name, final=bool(final),
            engine=engine.name, blocking_s=round(secs, 4),
        )
        return secs

    def sampler_epoch_of(step):
        bpe = sampler.batches_per_epoch
        return int(step) // bpe if bpe else 0

    # ---- resume (reference train.py:195-212; policy in _resume) ------------
    start_step = 0
    if config.resume_from_checkpoint:
        try:
            with telemetry.span("resume", metric="resume_s"):
                start_step, state = _resume(
                    config, exp_dir, state, sampler, engine, totals
                )
        except BaseException:
            # the teardown try/finally only starts after loader.start();
            # a failed resume (wrong model config, every-candidate-corrupt)
            # must not leak the async checkpointer's thread machinery in
            # long-lived callers
            engine.close()
            raise
    if start_step > 0 and prior_step is not None and prior_step > start_step:
        telemetry.emit(
            "resume_replay", start_step=start_step, prior_step=prior_step,
            replayed_steps=prior_step - start_step,
        )
    else:
        prior_step = None  # nothing to replay (fresh start / no progress record)

    # ---- goodput autopilot (--checkpoint-frequency auto) -------------------
    # telemetry-driven cadence: bootstrap folds every prior attempt's death
    # (hard kills, crashes, preemptions, hangs) from the telemetry stream
    # into the failure-history sidecar, then takes the initial Young-Daly
    # decision from the persisted estimates. The interval gates a
    # COLLECTIVE save, so decisions are host-0-computed and broadcast
    # inside decide() — every host agrees on every save step.
    autopilot = None
    ap_next_save = None
    if config.checkpoint_auto:
        from pyrecover_tpu.resilience.autopilot import CheckpointAutopilot

        autopilot = CheckpointAutopilot(
            exp_dir, engine=engine.name,
            static_interval=config.checkpoint_frequency,
            floor=config.ckpt_auto_floor,
            ceiling=config.ckpt_auto_ceiling,
            mtti_prior_s=config.ckpt_auto_mtti_prior_s,
            window=config.ckpt_auto_window,
            default_cost_s=config.default_ckpt_time,
            default_iter_s=config.default_iter_time,
        )
        ap_next_save = start_step + autopilot.bootstrap(
            telemetry_path, step=start_step
        )
    loader = DataLoader(
        dataset, sampler, pad_token_id=pad_token_id, mesh=mesh,
        prefetch=2, num_workers=4,
        stall_timeout=config.loader_stall_timeout,
    ).start()

    # everything past loader.start() runs under try/finally: an exception
    # anywhere below (setup included) must stop the prefetch threads and
    # any in-flight background save — the daemon flag covers process exit,
    # but long-lived callers (tests, the resilient-launcher loop) would
    # otherwise leak threads and queued device batches per failed attempt
    step = start_step
    stopped_early = False
    profiling = False
    prof_span = None
    run_eval = None
    watcher = None
    csv_logger = None
    # run-health watchdog: created now, STARTED only after the first
    # completed step of this attempt — the first step carries jit compile,
    # an arbitrarily long legitimate silence
    hang_watchdog = (
        telemetry.watchdog.Watchdog(config.hang_watchdog_timeout)
        if config.hang_watchdog_timeout > 0 else None
    )
    # per-dispatch implicit-transfer guard (events + typed error); "log"
    # mode instead wraps the whole loop in jax's stderr-logging guard
    dispatch_watch = (
        detectors.transfer_watch if config.transfer_guard == "disallow"
        else None
    )
    loop_guard = (
        jax.transfer_guard("log") if config.transfer_guard == "log"
        else contextlib.nullcontext()
    )
    pending_losses = []  # (step, loss device scalar) for the CSV

    def flush_csv():
        for s_, l_ in pending_losses:
            # jaxlint: disable-next=host-sync-in-hot-loop -- called only at
            # sync points; the loss sync there already drained the queue
            csv_logger.log(s_, float(l_))
        pending_losses.clear()
        # push the batch to the OS now: rows must not sit in the userspace
        # buffer until close() — a SIGTERM kill would lose every row since
        # the last sync point
        csv_logger.flush()

    try:
        def build_step(model_config):
            return make_train_step(
                model_config, optimizer,
                loss_chunk_size=config.loss_chunk_size,
                grad_accumulation_steps=config.grad_accumulation_steps,
                optimizer_sharding=config.optimizer_sharding,
                grad_allreduce=config.grad_allreduce,
                grad_quant_block=config.grad_quant_block,
                grad_bucket_mb=config.grad_bucket_mb,
            )

        from pyrecover_tpu.telemetry import stepscopes
        from pyrecover_tpu.utils.remat import CompiledOnce

        # compiled before its first call: under remat one rung leaner
        # where the compiler refuses the chosen one for memory, and in
        # every run the compiled step's operations by the program's own
        # scopes, for a profile's reader (tools/step_scopes.py)
        step_fn = CompiledOnce(
            build_step, model_config, remat_decision,
            on_ready=lambda decision: telemetry.emit(
                "remat_autosize", **decision.as_event()),
            scopes_path=exp_dir / stepscopes.FILE_NAME,
        )
        if config.grad_bucket_mb > 0:
            # one host-side record of the overlap configuration: the
            # bucket layout the step was built to issue (the same
            # trace-time metadata the jitted step resolves), so the
            # telemetry stream shows the effective layout without
            # anyone reading the jaxpr
            from pyrecover_tpu.parallel.collectives import (
                param_leaf_order,
                resolve_bucket_layout,
            )

            layout = resolve_bucket_layout(
                [int(x.size) for x in
                 jax.tree_util.tree_leaves(state.params)],
                config.grad_bucket_mb,
                int(dict(mesh.shape).get("data", 1)),
                config.grad_quant_block,
                order=param_leaf_order(state.params),
            )
            bucket_bytes = (
                [b.nbytes_f32 for b in layout] if layout else []
            )
            telemetry.emit(
                "grad_bucket",
                bucket_mb=float(config.grad_bucket_mb),
                mode=config.grad_allreduce,
                buckets=len(bucket_bytes),
                degenerate=layout is None,  # cap admitted one bucket:
                # the step kept the unbucketed single-collective form
                bucket_bytes_f32=bucket_bytes,
                max_bucket_bytes=max(bucket_bytes, default=0),
                min_bucket_bytes=min(bucket_bytes, default=0),
            )
        if config.grad_allreduce != "fp32" or (
            config.optimizer_sharding != "none"
        ):
            # one host-side record of the bandwidth-lean configuration —
            # modelled wire bytes for the gradient sync so a telemetry
            # stream (and the doctor/summarizer) can see what the step
            # was built to move without re-deriving the traffic model
            from pyrecover_tpu.parallel.collectives import (
                DEFAULT_QUANT_BLOCK,
                wire_bytes_per_element,
            )

            mesh_shape = dict(mesh.shape)
            replicas = int(mesh_shape.get("data", 1))
            grad_elems = sum(
                int(x.size) for x in jax.tree_util.tree_leaves(state.params)
            )
            grad_bytes = sum(
                int(x.size) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(state.params)
            )
            block = config.grad_quant_block or DEFAULT_QUANT_BLOCK
            bpe = wire_bytes_per_element(
                config.grad_allreduce, block,
                elem_bytes=grad_bytes / max(grad_elems, 1),
            )
            telemetry.emit(
                "grad_quantize",
                mode=config.grad_allreduce,
                optimizer_sharding=config.optimizer_sharding,
                block=int(block),
                data_replicas=replicas,
                error_feedback=config.grad_allreduce == "int8",
                grad_bytes_fp32=grad_bytes,
                wire_bytes_per_leg=int(grad_elems * bpe),
            )
        # recompile detector: an abstract-signature change on the jitted
        # step is a genuine retrace — one `recompile` event per drift, so
        # a recompile storm can't silently eat throughput
        step_fn = detectors.RecompileWatch(step_fn, name="train_step")
        # MFU/TFLOPs use the reference's 6N convention: token embedding
        # excluded (ref train.py:126-127), untied output projection kept.
        meter = ThroughputMeter(
            model_config,
            get_num_params(state.params, exclude_embedding=True),
            config.sequence_length,
            jax.device_count(),
        )
        csv_logger = LossCSVLogger(exp_dir, config.experiment_name,
                                   enabled=config.log_loss_to_csv,
                                   resume_step=start_step)
        run_eval = build_eval_runner(config, model_config, pad_token_id, mesh)
        watcher = PreemptionWatcher(
            enabled=config.timeaware_checkpointing,
            default_iter_time=config.default_iter_time,
            default_ckpt_time=config.default_ckpt_time,
            job_end_time=config.job_end_time,
            check_interval=config.preempt_check_interval,
        ).install_signal_handler().start_maintenance_watcher()

        # ---- hot loop (reference train.py:220-379) -------------------------
        # Device syncs (materializing the loss) and the cross-host stop
        # broadcast run only on logging/preempt-check steps — every other
        # step is pure async dispatch, so neither time-aware mode nor
        # --log-loss-to-csv taxes the hot path. ``pending_tokens`` /
        # ``pending_losses`` hold the per-step device scalars between syncs
        # (tiny arrays; materialized in one batch at the next sync point —
        # by then all but the newest are already computed).
        train_t0 = time.monotonic()
        # pre-loop warmup (mesh/model init, compile staging) — part of the
        # restart tax on a resumed run; the checkpoint load is its own bucket
        totals.setup_s = max(train_t0 - t_entry - totals.ckpt_load_s, 0.0)
        pending_tokens = []
        # (step, iter_t0, t_data, t_dispatch) monotonic stamps awaiting a
        # sync point — both the step_time events and the retroactive
        # step/data_wait/dispatch trace spans are written from this buffer
        step_times = []
        sync_t0 = time.monotonic()
        steps_since_sync = 0

        def close_interval(now):
            """Attribute the wall time since the last boundary to stepping
            (goodput ledger: productive vs replayed share) and flush the
            buffered per-step telemetry — host-side work only, no device
            syncs. Called at sync points and before eval/checkpoint blocks
            so their time never counts as stepping. Returns
            ``(interval_s, steps_in_interval)`` and resets the interval."""
            nonlocal sync_t0, steps_since_sync
            dt = now - sync_t0
            n = steps_since_sync
            if n > 0:
                totals.step_s += dt
                if prior_step is not None:
                    replayed = min(prior_step, step) - (step - n)
                    if replayed > 0:
                        totals.replayed_steps += replayed
                        totals.replayed_s += dt * replayed / n
            for s_, t0_, td_, tp_ in step_times:
                telemetry.emit(
                    "step_time", step=s_, data_wait_s=round(td_ - t0_, 6),
                    dispatch_s=round(tp_ - td_, 6),
                )
                # retroactive trace spans from the buffered stamps: the
                # hot loop never pays the span I/O, the trace still shows
                # per-step data-wait vs dispatch slices at the real times
                sid = telemetry.record_span("step", t0_, tp_, step=s_)
                telemetry.record_span(
                    "data_wait", t0_, td_, step=s_, parent=sid,
                    metric="step_data_wait_s",
                )
                telemetry.record_span(
                    "dispatch", td_, tp_, step=s_, parent=sid,
                    metric="step_dispatch_s",
                )
            step_times.clear()
            sync_t0 = now
            steps_since_sync = 0
            return dt, n

        with loop_guard, jax.sharding.set_mesh(mesh):
            while step < config.training_steps:
                if (
                    config.profile
                    and step == config.profile_step_start
                    and not profiling
                ):
                    # span wraps the whole profiler window so the JSONL
                    # trace and the jax profile correlate on the timeline
                    # a relative --profile-dir lies under the experiment
                    # directory, beside the step_scopes.json that reads it
                    # (tools/step_scopes.py); an absolute one is as given
                    profile_dir = exp_dir / config.profile_dir
                    prof_span = telemetry.spans.begin(
                        "jax_profile", dir=str(profile_dir),
                        start_step=step,
                    )
                    jax.profiler.start_trace(str(profile_dir))
                    profiling = True

                # fault seam: `sigterm_at_step N` delivers its signal as
                # step N begins, so the final checkpoint lands exactly at N
                faults.check("train_step", step=step + 1)
                iter_t0 = time.monotonic()
                epoch, batch = next(loader)
                t_data = time.monotonic()
                if dispatch_watch is None:
                    state, metrics = step_fn(state, batch)
                else:
                    with dispatch_watch(step=step + 1):
                        state, metrics = step_fn(state, batch)
                t_dispatch = time.monotonic()
                step += 1
                steps_since_sync += 1
                if hang_watchdog is not None:
                    hang_watchdog.beat("train_loop")
                    if not hang_watchdog.started:
                        hang_watchdog.start()  # first step done: compile over
                if telemetry.enabled():
                    # host-side timestamps only; under async dispatch
                    # dispatch_s is the enqueue cost, not device time —
                    # device time is the sync-interval average (train_sync)
                    # jaxlint: disable-next=untimed-device-work -- measuring
                    # the enqueue cost is the point; a block_until_ready here
                    # would serialize the hot loop it instruments
                    step_times.append((step, iter_t0, t_data, t_dispatch))
                pending_tokens.append(metrics["n_tokens"])
                if csv_logger.enabled:
                    pending_losses.append((step, metrics["loss"]))

                check_preempt = watcher.is_check_step(step)
                want_log = step % config.logging_frequency == 0
                if want_log or check_preempt:
                    t_sync0 = time.monotonic()
                    exit_stats = metrics.get("exit_stats")
                    if exit_stats is None:
                        # jaxlint: disable-next=host-sync-in-hot-loop -- THE
                        # deliberate once-per-interval sync: everything else
                        # batches to this point (ISSUE 2 allowlisted site)
                        loss = float(metrics["loss"])  # device sync
                    else:
                        # an exit-gated model: the loss comes up inside its
                        # vector of exit statistics, in the same one transfer
                        # jaxlint: disable-next=host-sync-in-hot-loop -- the
                        # same deliberate sync, in the loss's place
                        exit_stats = np.asarray(exit_stats).tolist()
                        loss = exit_stats[0]
                    sync_s = time.monotonic() - t_sync0
                    for t in pending_tokens:
                        # jaxlint: disable-next=host-sync-in-hot-loop -- the
                        # loss sync above already materialized these scalars
                        meter.update(int(t), config.batch_size)
                    pending_tokens.clear()
                    flush_csv()
                    snap = meter.log(step, epoch, loss) if want_log else None
                    # honest per-step time: interval average between sync
                    # points (per-step wall time under async dispatch
                    # measures only the dispatch, except on sync steps
                    # where it spikes)
                    dt, n = close_interval(time.monotonic())
                    watcher.observe_iter(dt / n)
                    if autopilot is not None:
                        # same interval-average feed; the autopilot's
                        # median estimator shrugs off the compile outlier
                        autopilot.observe_iter(dt / n, n=n, step=step)
                    # the deliberate sync is itself a trace slice, and the
                    # interval-average iter time feeds the step-time
                    # histogram (weight n: it stands in for n steps)
                    telemetry.record_span(
                        "loss_sync", t_sync0, t_sync0 + sync_s, step=step,
                    )
                    telemetry.metrics.histogram("step_iter_s").observe(
                        dt / n, n=n
                    )
                    # periodic HBM gauge sample (no-op where the backend
                    # exposes no memory_stats, i.e. CPU) — flushed with the
                    # metrics_snapshot below, peak folded into run_summary
                    detectors.sample_hbm()
                    telemetry.metrics.maybe_flush(
                        interval_s=config.metrics_flush_interval_s
                    )
                    telemetry.emit(
                        "train_sync", step=step, loss=round(loss, 6),
                        steps=n, interval_s=round(dt, 6),
                        iter_s=round(dt / n, 6), sync_s=round(sync_s, 6),
                        grad_accum_steps=config.grad_accumulation_steps,
                        **exit_stats_fields(exit_stats),
                    )
                    # live plane: the same derived numbers the throughput
                    # event carries, as gauges the exporter can serve
                    # between flushes (dict writes — no sync, no I/O)
                    telemetry.metrics.gauge("train_step").set(step)
                    if snap is not None:
                        for key, gauge_name in (
                            ("tokens_per_sec", "train_tokens_per_sec"),
                            ("mfu_pct", "train_mfu_pct"),
                            ("tflops", "train_tflops"),
                        ):
                            v = snap.get(key)
                            if isinstance(v, (int, float)):
                                telemetry.metrics.gauge(gauge_name).set(
                                    round(v, 4)
                                )
                        telemetry.emit(
                            "throughput", step=step,
                            **{
                                k: round(v, 4) if isinstance(v, float) else v
                                for k, v in snap.items()
                            },
                        )

                if config.profile and step == config.profile_step_end and profiling:
                    jax.profiler.stop_trace()
                    prof_span.end()
                    profiling = False

                # held-out evaluation (beyond-parity)
                if run_eval is not None and step % config.eval_frequency == 0:
                    close_interval(time.monotonic())
                    eval_t0 = time.monotonic()
                    with telemetry.span("eval", step=step, metric="eval_s"):
                        eval_loss = run_eval(state)
                    eval_s = time.monotonic() - eval_t0
                    totals.eval_s += eval_s
                    log_host0("eval | step %d | loss %.4f", step, eval_loss)
                    telemetry.emit(
                        "eval", step=step, loss=round(eval_loss, 6),
                        seconds=round(eval_s, 4),
                    )
                    # exclude eval wall time from iter-time learning AND the
                    # throughput window (else tok/s and MFU are understated)
                    sync_t0 = time.monotonic()
                    meter.reset()

                # periodic checkpoint (reference train.py:310-331). With
                # the autopilot, "periodic" is the adaptive interval: the
                # next save step is re-decided after every save from the
                # freshly observed cost + the live failure model.
                if autopilot is not None:
                    ckpt_due = step >= ap_next_save
                else:
                    ckpt_due = (
                        config.checkpoint_frequency > 0
                        and step % config.checkpoint_frequency == 0
                    )
                if ckpt_due and step < config.training_steps:
                    close_interval(time.monotonic())
                    secs = save_ckpt(step)
                    totals.ckpt_save_s += secs
                    watcher.observe_ckpt(secs)
                    if autopilot is not None:
                        autopilot.observe_save(secs)
                        ap_next_save = step + autopilot.decide(
                            step, source="post_save"
                        )
                    # don't attribute checkpoint time to iteration time
                    sync_t0 = time.monotonic()

                # time-aware stop (reference train.py:223-232, 342-375);
                # cheap host-local notice signals are observed every step,
                # the deadline/broadcast decision only on check steps
                if watcher.should_stop(step):
                    close_interval(time.monotonic())
                    secs = save_ckpt(step, final=True)
                    totals.ckpt_save_s += secs
                    stopped_early = True
                    break

        close_interval(time.monotonic())  # tail interval since the last sync
        totals.train_s = time.monotonic() - train_t0

        # final checkpoint at completion (`latest` is always the end state);
        # the autopilot never disables saves, whatever the static knob says
        if not stopped_early and (
            config.checkpoint_frequency > 0 or autopilot is not None
        ):
            secs = save_ckpt(step, final=True)
            totals.ckpt_save_s += secs
    finally:
        status["step"] = step  # crashed runs still report how far they got
        unwinding = sys.exc_info()[0] is not None
        if hang_watchdog is not None:
            hang_watchdog.stop()
        detectors.sample_hbm()  # final peak sample for run_summary
        if profiling:
            jax.profiler.stop_trace()
            prof_span.end()
        loader.stop()
        if run_eval is not None:
            run_eval.loader.stop()
        if watcher is not None:
            watcher.stop_maintenance_watcher()
        if csv_logger is not None:
            try:
                flush_csv()  # losses buffered since the last sync point
            except Exception:
                # the buffered device scalars may be poisoned by the very
                # error being unwound — dropping them must not mask it
                pending_losses.clear()
            csv_logger.close()
        try:
            # a failed background save must fail the run; the bounded
            # timeout keeps a wedged writer from hanging the unwind (the
            # daemon flag would then be what it was always meant to be:
            # the very last resort, after a loud TimeoutError)
            engine.join(timeout_s=_BG_JOIN_TIMEOUT_S)
        except Exception:
            if not unwinding:
                raise
            log_host0(
                "in-flight background checkpoint save also failed during "
                "error unwind", level=30,  # WARNING; the original error wins
            )
        finally:
            # every join's background seconds: the loop did not pay for them
            totals.ckpt_shadow_s += engine.shadow_s
        engine.close()
    write_requeue_marker(exp_dir, done=not stopped_early, step=step)
    status["status"] = "stopped_early" if stopped_early else "finished"
    status["step"] = step
    totals.wall_s = time.monotonic() - t_entry
    log_host0(
        "%s after step %d | %s",
        "Stopped early (deadline/preemption)" if stopped_early else "Finished",
        step, totals.summary(),
    )
    return state, step, stopped_early


def main(argv=None):
    config = get_args(argv)
    train(config)


def _process_age():
    """Seconds this process has lived (``/proc/self/stat``), or None."""
    try:
        stat = Path("/proc/self/stat").read_text()
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        up = float(Path("/proc/uptime").read_text().split()[0])
    except (OSError, ValueError, IndexError):
        return None
    return round(max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0), 3)


# the last statements of the module body: the instant its import ends
IMPORT_S = _process_age()
CKPT_MODULES = loaded_engine_modules()


if __name__ == "__main__":
    main(sys.argv[1:])
