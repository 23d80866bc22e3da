"""What the layer scan keeps for the backward sweep under ``remat``.

``remat: true`` wraps every block of the layer scan in ``jax.checkpoint``;
the backward sweep then recomputes the block's forward from its carry.
What it does NOT have to recompute is whatever the checkpoint policy
saves, and the values worth saving carry a ``checkpoint_name``:

    flash_out, flash_lse   the flash call's own residuals (ops/
                           flash_attention.py ``_flash_fwd``): the forward
                           kernel then runs once, not twice; lse is kept as
                           lane 0, (b, h, s)
    attn_q, attn_k, attn_v q, k, v after rope, as they enter the attention
                           call (models/llama.py ``_block``): the three
                           input products and the rope are not repeated
    attn_resid             the residual stream after the attention
                           sublayer: ``wo`` and the first norm are not
                           repeated
    ffn_w1, ffn_w3         the two SwiGLU products before the activation
                           (``ffn_sublayer``, dense path)
    ssm_in, ssm_conv,      a Mamba layer's mixer (models/mamba.py): the
    ssm_dt, ssm_y          input product (u and z), the convolved and
                           activated u, the float32 step, the scan's
                           output. Named and on no rung yet: a hybrid
                           stack is sized as it stands (``full`` at the
                           benchmark's shape), PERF.md section 7

``LADDER`` lists the save-sets worth choosing between, richest first:
``none`` (no remat at all) down to ``full`` (nothing kept; every block
recomputed). The rungs between are the ones on the frontier of MEASURED
milliseconds saved against GiB kept (tools/remat_ladder.py on a TPU v5
lite at the `mistral-7b.steady` shapes, PERF.md section 6, PR 31): the
flash residuals buy 18 ms a step for 0.49 GiB, q/k/v 32 ms for 0.75 GiB,
the ``w3`` product 34 ms for 1.75 GiB. ``attn_resid`` and ``ffn_w1`` are
named and on no rung: kept, they made the step slower or bought less than
``w3`` for the same bytes (the elementwise passes XLA then leaves unfused
cost what the skipped product saved); the tool's ``--sets`` measures any
combination of the names again.

``remat_policy: "auto"`` (the default reading of ``remat: true``) picks
the RICHEST rung whose modelled bytes fit what the compiler enforces for
the device kind, less a margin. The model is the shardcheck SC05 table
(analysis/shardcheck/checks.py ``memory_budget``), which is held by a
test to the v5e compiler's own peaks and errs high. Pure metadata
arithmetic, no device touched; the device kind comes from the caller
(the live accelerator in train/bench) or ``$PYRECOVER_DEVICE_KIND``. A
kind with no limit (the CPU, hardware nobody asked the compiler about)
gets ``full``: there is nothing to size against. ``remat: false`` means
no remat, and ``auto`` does not override it. The compiler keeps the last
word: :class:`CompiledOnce` steps down one rung where the chosen rung's
step does not compile.
"""

import dataclasses
import os

import jax

FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"
FLASH = (FLASH_OUT, FLASH_LSE)
QKV = ("attn_q", "attn_k", "attn_v")
W3 = ("ffn_w3",)

# rung -> the names the layer scan saves, from richest to leanest; "none"
# is no remat at all, every other rung rematerializes
LADDER = {
    "none": (),
    "flash+qkv+w3": FLASH + QKV + W3,
    "qkv+w3": QKV + W3,
    "flash+qkv": FLASH + QKV,
    "qkv": QKV,
    "save-attn": FLASH,
    "full": (),
}
RUNGS = tuple(LADDER)

# what the compiler lets one program use of a device's memory, by device
# kind (substring match, as the other tables): read off its own refusal
# ("Used 22.69G of 15.75G hbm", tools/remat_ladder.py)
COMPILER_LIMIT_BYTES = {
    "v5e": int(15.75 * 2**30),
    "v5litepod": int(15.75 * 2**30),
    "v5 lite": int(15.75 * 2**30),
}
# kept free below that limit: 1 GiB because the v5e compiler does not wait
# for its hard limit. Once a program's own peak passes ~14.7 GiB it
# rematerializes by itself (`.remat` clones of the SwiGLU products in the
# optimized HLO) down to what then reads as a peak of 14.6-15.6 GiB, and
# what it recomputes costs more than the rung saved (flash+w1+w3: 1,034 ms
# a step against full's 983; tools/remat_ladder.py counts the clones). The
# last 128 MiB are for the model's error on its low side (2 %, tests/
# test_remat_ladder.py) and for what lives on the device beside the step
# (the next batches, the loss scalars of the steps in flight).
MARGIN_BYTES = (1024 + 128) * 2**20

DEVICE_KIND_ENV = "PYRECOVER_DEVICE_KIND"

# batch-suggestion search bound: 8 doublings = 256x the configured batch
_MAX_BATCH_DOUBLINGS = 8


def saved_names(model_config):
    """The checkpoint names the layer scan of ``model_config`` keeps."""
    if model_config.remat_save is not None:
        return tuple(model_config.remat_save)
    return FLASH if model_config.remat_policy == "save-attn" else ()


def checkpoint_policy(model_config):
    """The ``jax.checkpoint`` policy of the layer scan: the one function
    both the scan (models/llama.py ``_stack``) and the 1f1b schedule
    (train_state.py) take theirs from."""
    names = saved_names(model_config)
    if names:
        return jax.checkpoint_policies.save_only_these_names(*names)
    return jax.checkpoint_policies.nothing_saveable


def named_bytes(model_config, names, *, tokens, itemsize, tensor=1):
    """Bytes ONE layer pass keeps on a device for the named values, at the
    layout the chip holds them in (a head's minor dimension padded to the
    128-lane tile). A name the model's program does not hold costs
    nothing: the flash names without the flash kernel, the SwiGLU names
    on the MoE path."""
    cfg = model_config
    head = -(-cfg.head_dim // 128) * 128 * itemsize
    q_heads = max(cfg.n_heads // tensor, 1)
    kv_heads = max(cfg.n_kv_heads // tensor, 1)
    flash = cfg.attention_impl == "flash"
    dense = cfg.n_experts == 0
    per_token = {
        "flash_out": q_heads * head if flash else 0,
        "flash_lse": q_heads * 4 if flash else 0,
        "attn_q": q_heads * head,
        "attn_k": kv_heads * head,
        "attn_v": kv_heads * head,
        "attn_resid": cfg.dim * itemsize,
        "ffn_w1": cfg.ffn_hidden_dim // tensor * itemsize if dense else 0,
        "ffn_w3": cfg.ffn_hidden_dim // tensor * itemsize if dense else 0,
        # a Mamba layer's mixer (models/mamba.py): u and z, the convolved
        # u, the float32 step and the scan's float32 output
        "ssm_in": 2 * cfg.d_inner // tensor * itemsize,
        "ssm_conv": cfg.d_inner // tensor * itemsize,
        "ssm_dt": cfg.d_inner // tensor * 4,
        "ssm_y": cfg.d_inner // tensor * 4,
    }
    return tokens * sum(per_token[name] for name in names)


def compiler_limit_bytes(device_kind):
    kind = (device_kind or "").lower()
    for key, limit in COMPILER_LIMIT_BYTES.items():
        if key in kind:
            return limit
    return None


def _with_rung(model_config, rung):
    return dataclasses.replace(
        model_config, remat=rung != "none", remat_save=LADDER[rung]
    )


@dataclasses.dataclass(frozen=True)
class RematDecision:
    """The resolved rung + the evidence it was sized on."""

    rung: str  # a name of LADDER
    fits: bool  # None = nothing to judge by (unknown kind, remat off)
    device_kind: str
    limit_bytes: int  # None when the device kind has no limit
    margin_bytes: int
    table: dict  # rung -> modelled total bytes/device (the SC05 rows)
    batch_size: int  # the configured GLOBAL batch
    batch_per_chip: int
    suggested_batch_size: int  # largest fitting GLOBAL batch, >= configured
    suggested_batch_per_chip: int
    suggested_total_bytes: int  # modelled bytes at the suggested batch
    fell_back: int = 0  # rungs stepped down after a refused compile
    compiled_peak_bytes: int = None  # the compiler's own, once compiled

    @property
    def remat(self):
        return self.rung != "none"

    @property
    def saved_names(self):
        return LADDER[self.rung]

    def apply(self, model_config):
        """``model_config`` as this decision builds it."""
        return _with_rung(model_config, self.rung)

    def stepped_down(self):
        """One rung leaner, after the compiler refused this one."""
        leaner = RUNGS[RUNGS.index(self.rung) + 1]
        return dataclasses.replace(
            self, rung=leaner, fell_back=self.fell_back + 1
        )

    def as_event(self):
        """Flat dict for the ``remat_autosize`` telemetry event."""
        return {
            "rung": self.rung,
            "saved_names": list(self.saved_names),
            "fits": self.fits,
            "device_kind": self.device_kind,
            "limit_bytes": self.limit_bytes,
            "margin_bytes": self.margin_bytes,
            "modelled_bytes": dict(self.table),
            "fell_back": self.fell_back,
            "compiled_peak_bytes": self.compiled_peak_bytes,
            "batch_size": self.batch_size,
            "batch_per_chip": self.batch_per_chip,
            "suggested_batch_size": self.suggested_batch_size,
            "suggested_batch_per_chip": self.suggested_batch_per_chip,
            "suggested_total_bytes": self.suggested_total_bytes,
        }


def modelled_total_bytes(model_config, mesh_shape, *, batch_size, seq_len,
                         rung=None, loss_chunk_size=0,
                         optimizer_sharding="none", grad_allreduce="fp32",
                         quant_block=256, state=None):
    """Per-device HBM estimate of the train step — exactly the SC05 table
    (memory_budget) — for ``model_config`` as it stands or on ``rung``,
    with the state leaves resolved in the configured bandwidth-lean modes
    (zero1-sharded moments, the int8 residual). ``state`` takes the
    ``(leaves, specs)`` of an earlier call: they depend on neither the
    rung nor the batch."""
    from pyrecover_tpu.analysis.shardcheck.checks import memory_budget

    leaves, specs = state or _abstract_state(
        model_config, mesh_shape, optimizer_sharding, grad_allreduce,
        quant_block,
    )
    if rung is not None:
        model_config = _with_rung(model_config, rung)
    rows, _ = memory_budget(
        leaves, specs, mesh_shape, model_config, batch_size=batch_size,
        seq_len=seq_len, loss_chunk_size=loss_chunk_size,
    )
    return int(rows["total_bytes"])


def _abstract_state(model_config, mesh_shape, optimizer_sharding,
                    grad_allreduce, quant_block):
    from pyrecover_tpu.analysis.shardcheck.runner import abstract_state_leaves

    return abstract_state_leaves(
        model_config, optimizer_sharding=optimizer_sharding,
        grad_allreduce=grad_allreduce, quant_block=quant_block,
        mesh_shape=mesh_shape,
    )


# The $PYRECOVER_DEVICE_KIND env override below is a fleet-uniform launch
# contract (the PR 7 elastic-preflight convention): every host of one job
# is launched with the same value, so the resolved rung is identical
# everywhere — which is what the congruence marker declares.
# distcheck: congruent -- config + fleet-uniform $PYRECOVER_DEVICE_KIND only
def resolve_remat_policy(model_config, mesh_shape, *, batch_size, seq_len,
                         loss_chunk_size=0, optimizer_sharding="none",
                         grad_allreduce="fp32", quant_block=256,
                         device_kind=None):
    """Resolve ``model_config.remat_policy`` to a rung of ``LADDER``.

    Returns a :class:`RematDecision`; ``decision.apply(model_config)`` is
    the configuration to build. ``device_kind`` defaults to
    ``$PYRECOVER_DEVICE_KIND``; callers pass the live accelerator's kind.
    ``remat: false`` is ``none`` and an explicit ``full`` / ``save-attn``
    is that rung, whatever fits. Under ``auto`` the rungs are tried
    richest first and the first whose modelled bytes fit the compiler's
    limit less the margin wins; when nothing fits, ``full`` (the leanest
    the model can run) with ``fits=False``, so the launch preflight's
    SC05 and the compiler still get the last word. The same inputs give
    the same rung: a resumed run chooses what the interrupted one chose.
    """
    # env override WINS over the live device (the PR 7 elastic-preflight
    # convention): a CPU test host can size against real TPU limits
    device_kind = os.environ.get(DEVICE_KIND_ENV) or device_kind or ""
    limit = compiler_limit_bytes(device_kind)
    room = None if limit is None else limit - MARGIN_BYTES

    state = _abstract_state(
        model_config, mesh_shape, optimizer_sharding, grad_allreduce,
        quant_block,
    )

    def total_at(rung, batch):
        return modelled_total_bytes(
            model_config, mesh_shape, batch_size=batch, seq_len=seq_len,
            rung=rung, loss_chunk_size=loss_chunk_size, state=state,
        )

    table = {rung: total_at(rung, batch_size) for rung in RUNGS}
    batch_shards = max(
        int(mesh_shape.get("data", 1)) * int(mesh_shape.get("fsdp", 1)), 1
    )
    per_chip = max(int(batch_size) // batch_shards, 1)

    fits = None
    if not model_config.remat:
        chosen = "none"
    elif model_config.remat_policy != "auto":
        chosen = model_config.remat_policy  # "full" | "save-attn"
    elif room is None:
        chosen = "full"  # nothing to size against: today's program
    else:
        chosen = next((r for r in RUNGS if table[r] <= room), None)
        fits = chosen is not None
        chosen = chosen or "full"

    # spend what is left: largest doubling of the global batch the chosen
    # rung still fits (doubling preserves mesh divisibility)
    suggested, suggested_bytes = int(batch_size), table[chosen]
    if fits:
        batch = int(batch_size)
        for _ in range(_MAX_BATCH_DOUBLINGS):
            total = total_at(chosen, batch * 2)
            if total > room:
                break
            batch *= 2
            suggested, suggested_bytes = batch, total

    return RematDecision(
        rung=chosen, fits=fits, device_kind=device_kind, limit_bytes=limit,
        margin_bytes=MARGIN_BYTES, table=table,
        batch_size=int(batch_size), batch_per_chip=per_chip,
        suggested_batch_size=suggested,
        suggested_batch_per_chip=max(suggested // batch_shards, 1),
        suggested_total_bytes=suggested_bytes,
    )


class CompiledOnce:
    """The train step, compiled before its first call, with the compiler
    as the last word on the rung: where the step of ``decision``'s rung is
    refused with ``RESOURCE_EXHAUSTED`` the step is rebuilt one rung
    leaner (``build(model_config)``) and compiled again, down to ``full``
    at the latest, so it never loops. A failed compile is not cached and
    is paid by every run: this is the safety net, not the method.

    The compile is the one the first call would have made (jit finds the
    executable again), so reading the compiler's peak costs nothing.
    ``on_ready(decision)`` is called once, with the final decision; a run
    without remat has no decision (``None``), nothing to step down to and
    no such call. While a telemetry sink is registered the compiled
    step's table of scopes (``telemetry/stepscopes.py``) is written to
    ``scopes_path`` and named by ONE ``step_scopes`` event.
    """

    def __init__(self, build, model_config, decision, on_ready,
                 scopes_path=None):
        self.build = build
        self.model_config = model_config
        self.decision = decision
        self.on_ready = on_ready
        self.scopes_path = scopes_path
        self.fn = build(
            decision.apply(model_config) if decision else model_config)
        self._compiled = False

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def _compile(self, state, batch):
        from pyrecover_tpu.utils.logging import log_host0

        # an explicit policy is the user's: refused, it fails in words
        may_step_down = (
            self.decision is not None
            and self.model_config.remat_policy == "auto"
        )
        compiled = None
        # (a wrapper round the step that is no jitted function, as a test
        # plants, has nothing to compile ahead: its first call compiles)
        while hasattr(self.fn, "lower"):
            try:
                compiled = self.fn.lower(state, batch).compile()
                break
            except jax.errors.JaxRuntimeError as err:
                if not (
                    may_step_down and "RESOURCE_EXHAUSTED" in str(err)
                    and self.decision.rung != "full"
                ):
                    raise
                refused = self.decision.rung
                self.decision = self.decision.stepped_down()
                log_host0(
                    "remat: the compiler refused rung %s for memory; "
                    "stepping down to %s (a failed compile is paid every "
                    "run: the memory model wants repair)",
                    refused, self.decision.rung, level=30,
                )
                self.fn = self.build(self.decision.apply(self.model_config))
        if compiled is not None:
            self._write_scopes(compiled)
        if self.decision is None:
            return
        peak = None
        if compiled is not None:
            analysis = compiled.memory_analysis()
            peak = getattr(analysis, "peak_memory_in_bytes", None)
        self.decision = dataclasses.replace(
            self.decision, compiled_peak_bytes=peak
        )
        self.on_ready(self.decision)

    def _write_scopes(self, compiled):
        from pyrecover_tpu import telemetry
        from pyrecover_tpu.telemetry import stepscopes
        from pyrecover_tpu.utils.logging import log_host0

        if not (self.scopes_path and telemetry.enabled()
                and jax.process_index() == 0):
            return
        try:
            stepscopes.write(compiled, self.scopes_path)
        except Exception as err:  # a profile reader's aid must not stop a run
            log_host0(
                "step_scopes: no table of the compiled step (%s: %s)",
                type(err).__name__, err, level=30,
            )

    def __call__(self, state, batch):
        if not self._compiled:
            self._compiled = True
            self._compile(state, batch)
        return self.fn(state, batch)
