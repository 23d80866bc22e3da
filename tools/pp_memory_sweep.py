"""Compiled-memory sweep: 1F1B-at-high-M vs GPipe+accumulation.

`make_train_step` rejects grad accumulation under the 1F1B schedule with
"raise --pp-microbatches instead" (train_state.py) — 1F1B's microbatches
ARE the accumulation. This sweep quantifies that guidance in THREE
regimes, on the virtual CPU mesh via XLA's compiled `memory_analysis`
(the same measurement `tests/test_pipeline.py::
test_1f1b_reduces_peak_memory_remat_off` pins):

  A. fixed GLOBAL batch, rising M: 1F1B's per-stage boundary residency is
     2·(M/S) microbatches, but microbatch size shrinks as 1/M — boundary
     BYTES are M-independent (2·B·seq·dim/S), so raising M is memory-free
     and only reduces the bubble.
  B. fixed MICROBATCH size, batch grown via M (1F1B) vs via accumulation
     passes (GPipe at fixed M0): here 1F1B's boundary bytes DO grow
     linearly with the batch while GPipe+accum's pipeline stays
     constant-size — the regime where a crossover can exist.
  C. interleaving cost: plain 1F1B vs --pp-virtual-stages V at fixed
     batch — bubble halves by construction and per-tick vjp transients
     shrink with the 1/V chunk size.

Run:
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tools/pp_memory_sweep.py

Prints markdown tables (PARITY.md carries the committed copy) and a JSON
line with the raw numbers.
"""

import dataclasses
import json

import jax

from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.data import DataLoader, StatefulSampler, SyntheticTextDataset
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.parallel.mesh import MeshConfig, create_mesh
from pyrecover_tpu.train import init_sharded_state
from pyrecover_tpu.train_state import make_train_step

SEQ = 32
STAGES = 4
BASE_M = 8  # GPipe's fixed pipeline depth; accumulation provides the rest
VIRTUAL = 2  # regime C's interleaving factor (--pp-virtual-stages)


def measure(mesh, model_cfg, batch, accum):
    # the model cfg is used DIRECTLY (as tests/test_pipeline.py does):
    # routing it through TrainConfig.__post_init__ would overwrite
    # pp_schedule/pp_microbatches with the TrainConfig defaults
    train_cfg = TrainConfig(
        sequence_length=SEQ, batch_size=batch, learning_rate=1e-3
    )
    optimizer, _ = build_optimizer(train_cfg)
    state = init_sharded_state(jax.random.key(0), model_cfg, optimizer, mesh)
    ds = SyntheticTextDataset(
        num_samples=batch, seq_len=SEQ, vocab_size=model_cfg.vocab_size, seed=3
    )
    sampler = StatefulSampler(dataset_len=batch, global_batch_size=batch, seed=3)
    loader = DataLoader(ds, sampler, pad_token_id=0, mesh=mesh, prefetch=0)
    step = make_train_step(
        model_cfg, optimizer, donate=False, grad_accumulation_steps=accum
    )
    with jax.sharding.set_mesh(mesh):
        _, batch_arrays = next(loader)
        compiled = step.lower(state, batch_arrays).compile()
    mem = compiled.memory_analysis()
    return int(mem.temp_size_in_bytes)


def sweep(mesh, base, points):
    """points: (label, batch, M_1f1b, accum_gpipe). GPipe runs BASE_M
    microbatches per accumulation pass."""
    rows = []
    for label, batch, m, accum in points:
        one_f1b = measure(
            mesh,
            dataclasses.replace(base, pp_microbatches=m, pp_schedule="1f1b"),
            batch, accum=1,
        )
        gpipe_accum = measure(
            mesh,
            dataclasses.replace(
                base, pp_microbatches=BASE_M, pp_schedule="gpipe"
            ),
            batch, accum=accum,
        )
        rows.append({
            "label": label, "batch": batch, "M": m, "accum": accum,
            "temp_1f1b_mb": round(one_f1b / 1e6, 2),
            "temp_gpipe_accum_mb": round(gpipe_accum / 1e6, 2),
            "ratio_1f1b_over_gpipe": round(one_f1b / gpipe_accum, 3),
        })
    print("| point | batch | 1F1B M | GPipe accum | 1F1B MB | GPipe MB | ratio |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['label']} | {r['batch']} | {r['M']} | ×{r['accum']} "
            f"| {r['temp_1f1b_mb']} | {r['temp_gpipe_accum_mb']} "
            f"| {r['ratio_1f1b_over_gpipe']} |"
        )
    return rows


def main():
    assert len(jax.devices()) >= 2 * STAGES, (
        f"need {2 * STAGES} virtual devices; set XLA_FLAGS="
        f"--xla_force_host_platform_device_count={2 * STAGES}"
    )
    mesh = create_mesh(
        MeshConfig(data=len(jax.devices()) // STAGES, pipeline=STAGES)
    )
    base = dataclasses.replace(
        ModelConfig().tiny(max_seq_len=SEQ, vocab_size=128, n_layers=4),
        remat=False,
    )
    print("Regime A — fixed global batch 64, accumulation via M vs passes:")
    rows_a = sweep(mesh, base, [
        (f"B64/M{m}", 64, m, m // BASE_M) for m in (8, 16, 32, 64)
    ])
    print()
    print("Regime B — fixed microbatch size (2 rows), batch grown via M "
          "vs via passes:")
    rows_b = sweep(mesh, base, [
        (f"B{16 * s}/M{BASE_M * s}", 16 * s, BASE_M * s, s)
        for s in (1, 2, 4, 8)
    ])
    print()
    print(f"Regime C — interleaving cost: plain 1F1B vs --pp-virtual-stages "
          f"{VIRTUAL} ({STAGES * VIRTUAL} layers so chunks divide; fixed "
          "batch 64):")
    base_c = dataclasses.replace(base, n_layers=STAGES * VIRTUAL)
    rows_c = []
    for m in (8, 16, 32):
        v1 = measure(
            mesh,
            dataclasses.replace(base_c, pp_microbatches=m, pp_schedule="1f1b"),
            64, accum=1,
        )
        v2 = measure(
            mesh,
            dataclasses.replace(
                base_c, pp_microbatches=m, pp_schedule="1f1b",
                pp_virtual_stages=VIRTUAL,
            ),
            64, accum=1,
        )
        rows_c.append({
            "M": m, "temp_v1_mb": round(v1 / 1e6, 2),
            "temp_v2_mb": round(v2 / 1e6, 2),
            "ratio_v2_over_v1": round(v2 / v1, 3),
            "bubble_v1": round((STAGES - 1) / (m + STAGES - 1), 3),
            "bubble_v2": round(
                (STAGES - 1) / (VIRTUAL * m + STAGES - 1), 3
            ),
        })
    print("| M | V=1 temp MB | V=2 temp MB | ratio | bubble V=1 → V=2 |")
    print("|---|---|---|---|---|")
    for r in rows_c:
        print(
            f"| {r['M']} | {r['temp_v1_mb']} | {r['temp_v2_mb']} "
            f"| {r['ratio_v2_over_v1']} "
            f"| {r['bubble_v1']} → {r['bubble_v2']} |"
        )
    print(json.dumps({"stages": STAGES, "base_m": BASE_M,
                      "regime_a": rows_a, "regime_b": rows_b,
                      "regime_c": rows_c}))


if __name__ == "__main__":
    main()
