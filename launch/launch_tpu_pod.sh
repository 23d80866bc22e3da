#!/usr/bin/env bash
# Cloud TPU pod / queued-resource launcher.
#
# The TPU-native analogue of the reference's SLURM stack (L6): instead of
# sbatch+srun+NCCL rendezvous, a queued resource grants a TPU slice, the
# same command starts on every worker, and jax.distributed.initialize()
# inside the trainer discovers the topology from the TPU runtime.
# Preemption resilience comes from three layers:
#   1. --timeaware-checkpointing + SIGTERM handler → final sharded save;
#   2. run_resilient.sh on each worker → in-place resume while the slice
#      lives;
#   3. the queued resource itself → Google re-provisions evicted slices,
#      workers restart this script, and --resume-from-checkpoint=latest
#      picks up from the shared checkpoint dir (GCS or NFS).
#
# One-time provisioning (run from a workstation with gcloud):
#   gcloud compute tpus queued-resources create "$QR_NAME" \
#     --node-id "$TPU_NAME" --zone "$ZONE" \
#     --accelerator-type v5litepod-64 --runtime-version v2-alpha-tpuv5-lite \
#     [--best-effort | --spot]   # preemptible — the case this repo exists for
#
# Launch on every worker:
#   gcloud compute tpus tpu-vm ssh "$TPU_NAME" --zone "$ZONE" --worker=all \
#     --command "cd ~/pyrecover_tpu && bash launch/launch_tpu_pod.sh \
#                --checkpoint-dir gs://my-bucket/ckpts --checkpoint-engine sharded \
#                --experiment_name myrun"

set -euo pipefail
SCRIPT_DIR="$(cd "$(dirname "${BASH_SOURCE[0]}")" && pwd)"

# Cloud TPU sends SIGTERM ahead of maintenance/eviction; the trainer's
# signal handler (pyrecover_tpu/preempt.py install_signal_handler) turns it
# into a final checkpoint. Nothing to configure here — just don't trap it.

exec bash "${SCRIPT_DIR}/run_resilient.sh" \
  --timeaware-checkpointing \
  --checkpoint-engine sharded \
  "$@"
