"""One measured window of the trainer's own loop, in this process.

The entry the window drives is ``pyrecover_tpu.train.train(config)``: the
loader, the jitted step from ``train_state.make_train_step``, the periodic
loss sync and ``save_ckpt``. Nothing of that loop is copied. The harness
stands at three boundaries the trainer already has:

* a telemetry sink (the trainer's own event bus): it sees ``train_sync``,
  ``ckpt_saved``, ``step_time`` and ``recompile`` events with the host clock,
  opens and closes the window on them, and ends the run by lowering
  ``config.training_steps`` to the step just synced, which the loop's
  ``while step < config.training_steps`` reads on every pass;
* a wrapper round the step function ``make_train_step`` returns: for the
  first steps (set-up) it keeps what the comparison needs: the rows fed, the
  losses, the first gradient's norms as the optimizer holds them
  (``mu / (1 - b1)``), and the norms of the parameters' change; afterwards it
  only passes the call through. The object is the one the window then drives;
* ``jax.monitoring``: compilations inside the window are counted.

The workload file says which events open and close the window, so a steady
window (sync to sync) and a window of whole save cycles (sync, 8 steps, save
returned) are the same code.
"""

import gc
import glob
import math
import os
import time

import numpy as np

SEED_MOD = 2147483629  # a prime under 2**31: the trainer's seed is an int32


class Refused(Exception):
    """The run cannot be measured (wrong device, missing file...)."""


def flat(tree, prefix=""):
    """Nested dicts of leaves -> {'a/b': leaf}."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat(v, name + "/"))
        else:
            out[name] = v
    return out


def model_config(cfg):
    """A configuration file's (Hugging Face named) sizes as the trainer's
    ``ModelConfig``; the feed-forward width must come out as published."""
    from pyrecover_tpu.models.llama import ModelConfig

    kw = dict(
        dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
    )
    if cfg.get("num_local_experts"):
        kw.update(
            n_experts=cfg["num_local_experts"],
            moe_top_k=cfg["num_experts_per_tok"],
            moe_ffn_hidden=cfg["intermediate_size"],
            moe_aux_weight=cfg["router_aux_loss_coef"],
        )
    kw.update(cfg.get("trainer_model", {}))
    mc = ModelConfig(**kw)
    width = mc.expert_hidden_dim if mc.n_experts else mc.ffn_hidden_dim
    if width != cfg["intermediate_size"]:
        raise Refused(
            f"trainer's feed-forward width {width} is not the published "
            f"{cfg['intermediate_size']}")
    if mc.head_dim != cfg.get("head_dim", mc.head_dim):
        raise Refused("head size differs from the configuration's")
    return mc


def train_config(cell, cfg, seed, ckpt_dir):
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.parallel.mesh import MeshConfig

    return TrainConfig(
        model=model_config(cfg), mesh=MeshConfig(**cell.get("mesh", {})),
        sequence_length=cell["sequence_length"], batch_size=cell["batch_size"],
        seed=int(seed) % SEED_MOD, training_steps=1_000_000,
        training_samples=cell["batch_size"] * 4096,
        checkpoint_dir=str(ckpt_dir), experiment_name="bench",
        **cell["trainer"],
    )


def optimizer_facts(config):
    """What the reference needs to know of the optimizer, read off the
    trainer's configuration (eps is fixed in ``optim.build_optimizer``)."""
    if config.lr_schedule != "constant":
        raise Refused("the reference knows the warmup-constant schedule only")
    return {
        "learning_rate": config.learning_rate,
        "lr_warmup_steps": config.lr_warmup_steps,
        "adam_b1": config.adam_b1, "adam_b2": config.adam_b2,
        "adam_eps": 1e-8, "weight_decay": config.weight_decay,
        "grad_clipping": config.grad_clipping,
        "grad_max_norm": config.grad_max_norm,
        "param_dtype": config.model.param_dtype,
    }


def _sq_norms():
    """jitted {name: leaf} -> {name: sum of squares (per layer if stacked)}."""
    import jax
    import jax.numpy as jnp

    def sq(tree):
        out = {}
        for k, a in tree.items():
            a = a.astype(jnp.float32)
            ax = tuple(range(1, a.ndim)) if k.startswith("layers/") else None
            out[k] = jnp.sum(a * a, axis=ax)
        return out

    return jax.jit(sq)


class Probe:
    """The step function, with the first ``n`` calls observed."""

    def __init__(self, fn, n, config, enable_saves=None):
        self.fn, self.n, self.config = fn, n, config
        self.enable_saves = enable_saves  # (call index, frequency) or None
        self.calls = 0
        self.batches, self.losses, self.gnorms = [], [], []
        self.p0 = self.p0_sq = self.mu_sq = self.dp_sq = None
        self.rows_differ = True
        self.b1 = config.adam_b1
        self.marks = {}

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def __call__(self, state, batch):
        i = self.calls
        self.calls += 1
        if self.enable_saves and self.calls == self.enable_saves[0]:
            self.config.checkpoint_frequency = self.enable_saves[1]
        if i >= self.n:
            return self.fn(state, batch)
        import jax

        sq = _sq_norms()
        rows = {k: np.asarray(jax.device_get(batch[k]))
                for k in ("inputs", "labels")}
        self.batches.append(rows)
        seen = np.concatenate([b["inputs"] for b in self.batches])
        self.rows_differ = len({r.tobytes() for r in seen}) == len(seen)
        if i == 0:
            self.marks["first_call"] = time.monotonic()
            self.p0 = {k: np.asarray(v) for k, v in
                       flat(jax.device_get(state.params)).items()}
            self.p0_sq = sq(flat(state.params))
            self.marks["weights_copied"] = time.monotonic()
        new_state, metrics = self.fn(state, batch)
        if i == 0:
            self.marks["first_step_enqueued"] = time.monotonic()
        self.losses.append(metrics["loss"])
        self.gnorms.append(metrics["grad_norm"])
        if i == 0:
            mus = jax.tree_util.tree_leaves(
                new_state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
            mu = next(x.mu for x in mus if hasattr(x, "mu"))
            self.mu_sq = sq(flat(mu))
        if i == self.n - 1:
            import jax.numpy as jnp

            # the step's temporaries are gone before a leaf of the seed's
            # weights comes back up (a sharded model fills the chip)
            jax.block_until_ready(new_state.params)
            self.marks["change_begin"] = time.monotonic()
            self.dp_sq = {}
            for k, a in flat(new_state.params).items():
                ax = tuple(range(1, a.ndim)) if k.startswith("layers/") else None
                b = jax.device_put(self.p0[k], a.sharding)
                self.dp_sq[k] = np.asarray(
                    jax.jit(lambda a, b, ax=ax: jnp.sum(
                        (a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2,
                        axis=ax))(a, b), np.float64)
                del b
            self.p0 = None
            self.marks["change_done"] = time.monotonic()
        return new_state, metrics

    def readings(self):
        """Host numbers, once the steps have run."""
        to = lambda d: {k: np.sqrt(np.asarray(v, np.float64))
                        for k, v in d.items()}
        return {
            "loss": [float(x) for x in self.losses],
            "grad_norm": [float(x) for x in self.gnorms],
            "weight_leaf_norms": to(self.p0_sq),
            "grad_leaf_norms": {k: v / (1 - self.b1)
                                for k, v in to(self.mu_sq).items()},
            "change_leaf_norms": to(self.dp_sq),
        }


class WindowSink:
    """Opens and closes the window on the trainer's own events."""

    def __init__(self, config, spec, seconds, trace_dir=None):
        self.config, self.spec, self.seconds = config, spec, float(seconds)
        self.trace_dir = trace_dir
        self.records = []
        self.t_open = self.t_close = None
        self.step_open = self.step_close = None
        self.trace = None  # {"t0": mono, "t1": mono, "anchor_ns": ...}
        self.compiles = []  # monotonic stamps of backend compilations

    def write(self, rec):
        now = time.monotonic()
        self.records.append((now, rec))
        ev, step = rec.get("event"), rec.get("step")
        if self.t_open is None:
            if ev == self.spec["open_event"] and step == self.spec["open_step"]:
                self.t_open, self.step_open = now, step
                if self.trace_dir:
                    self._start_trace()
                    self.t_open = time.monotonic()
            return
        if self.t_close is not None or ev != self.spec["close_event"]:
            return
        if self.trace and self.trace.get("t1") is None:
            # a traced run: the profiler was on from the opening to here (a
            # few steps, or one save cycle) and stopping it costs seconds.
            # The measured window starts over, so that the rate the telemetry
            # readers see holds no profiler.
            self._stop_trace()
            self.t_open, self.step_open = time.monotonic(), step
            return
        if now - self.t_open >= self.seconds:
            self.t_close, self.step_close = now, step
            # the loop's own stops: no step past this one, no final save
            self.config.training_steps = int(step)
            self.config.checkpoint_frequency = -1

    def _start_trace(self):
        import jax

        kw = {}
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            kw["profiler_options"] = opts
        except Exception:
            pass
        jax.profiler.start_trace(self.trace_dir, **kw)
        with jax.profiler.TraceAnnotation("bench_anchor"):
            anchor = time.monotonic_ns()
        self.trace = {"t0": time.monotonic(), "anchor_ns": anchor, "t1": None}

    def _stop_trace(self):
        import jax

        self.trace["t1"] = time.monotonic()
        jax.profiler.stop_trace()

    def close(self):
        pass

    # -- what the window held -------------------------------------------------
    def in_window(self, event):
        return [r for t, r in self.records
                if r.get("event") == event and self.t_open < t <= self.t_close]

    def host_spans(self):
        """(name, t0, t1) on the monotonic clock: the trainer's own spans
        (``span`` events written after the fact, and ``span_end`` events),
        for naming the device's idle gaps."""
        out = []
        for _, r in self.records:
            ev = r.get("event")
            if ev == "span":
                out.append((r["name"], r["mono"], r["mono"] + r["dur_s"]))
            elif ev == "span_end":
                out.append((r["name"], r["mono"] - r["dur_s"], r["mono"]))
        return out


def run(ctx):
    """ctx: manifest, cell, cfg, seed, seconds, trace, t_start, peaks."""
    import jax

    cell, cfg = ctx["cell"], ctx["cfg"]
    chips = cell["chips"]
    check = cell["check"]
    work = ctx["work"]  # the caller's temporary directory, removed by it
    marks = ctx["marks"]
    import pyrecover_tpu.train as trainer
    from pyrecover_tpu import telemetry

    marks["imports_done"] = time.monotonic()
    config = train_config(cell, cfg, ctx["seed"], os.path.join(work, "ckpt"))
    spec = cell["window"]
    trace_dir = os.path.join(work, "trace") if ctx["trace"] else None
    sink = WindowSink(config, spec, ctx["seconds"], trace_dir)
    probes = []
    make = trainer.make_train_step

    def make_probed(*a, **kw):
        saves = spec.get("enable_saves")
        fn = make(*a, **kw)
        if ctx.get("fault"):  # tests and calibration only: break the path
            from benchmark.lib.faults import FAULTS

            fn = FAULTS[ctx["fault"]](fn, chips)
        probes.append(Probe(
            fn, check["reference_steps"], config,
            (saves["at_call"], saves["frequency"]) if saves else None))
        return probes[-1]

    def on_compile(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            sink.compiles.append(time.monotonic())

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    trainer.make_train_step = make_probed
    telemetry.add_sink(sink)
    try:
        state, step, _ = trainer.train(config)
    finally:
        trainer.make_train_step = make
        telemetry.remove_sink(sink)
    if sink.t_close is None:
        raise Refused("the window never closed")
    probe = probes[0]
    marks.update(probe.marks)
    marks["train_returned"] = time.monotonic()
    for t, r in sink.records:
        if r.get("event") == "run_start":
            marks["run_start_event"] = t

    # ---- the window -------------------------------------------------------
    steps = sink.step_close - sink.step_open
    seconds = sink.t_close - sink.t_open
    tokens = steps * cell["batch_size"] * cell["sequence_length"]
    syncs = sink.in_window("train_sync")
    failed = sum(1 for r in syncs if not math.isfinite(r.get("loss", 0.0)))
    result = {
        "steps": steps, "seconds": seconds, "tokens": tokens, "chips": chips,
        "failed": failed, "setup_s": sink.t_open - ctx["t_start"],
        "rate": tokens / seconds / chips, "sink": sink, "config": config,
        "recompiles": len(sink.in_window("recompile")),
        "compiles_in_window": sum(
            1 for t in sink.compiles if sink.t_open < t <= sink.t_close),
        "hbm_peak_bytes": max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()),
        "trace_file": None, "post": {},
    }
    if trace_dir:
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        result["trace_file"] = found[0] if found else None
    numbers = {}

    # ---- what the last save wrote, read back ---------------------------------
    if check.get("readback"):
        t0 = time.monotonic()
        numbers.update(readback(sink, config, state, jax))
        result["post"]["readback_s"] = time.monotonic() - t0

    # ---- the plain reference, once the program's state is freed --------------
    readings = probe.readings()
    rows = probe.batches
    rows_differ = probe.rows_differ
    del state, probe, probes
    gc.collect()
    t0 = time.monotonic()
    ref_out = ctx.get("reference_out")  # calibration: one reference a seed
    if ref_out is None:
        ref = ctx["manifest"].reference(cfg["reference"]).Reference(
            cfg, optimizer_facts(config), jax.devices()[:chips],
            precision=ctx.get("reference_precision", "f32"))
        ref_out = follow(ref, config.seed, rows)
        del ref
        gc.collect()
    result["post"]["reference_s"] = time.monotonic() - t0
    result["rows"] = rows
    numbers.update(compare(readings, ref_out))
    numbers["rows_repeated"] = 0.0 if rows_differ else 1.0
    numbers["recompiles_in_window"] = float(result["recompiles"])
    result["numbers"] = numbers
    result["readings"] = {"program": readings, "reference": ref_out}
    return result


def follow(ref, seed, rows):
    """Drive a reference through the rows the step was fed."""
    ref.init(seed)
    out = {"weight_leaf_norms": ref.weight_norms(), "loss": [], "grad_norm": []}
    for i, r in enumerate(rows):
        s = ref.step(r["inputs"], r["labels"])
        out["loss"].append(s["loss"])
        out["grad_norm"].append(s["grad_norm"])
        if i == 0:
            out["grad_leaf_norms"] = s["grad_leaf_norms"]
    out["change_leaf_norms"] = ref.change_norms()
    return out


def _units(d):
    """{'layers/wq': array(L), 'output': scalar} -> {'layers/wq[0]': x, ...}"""
    out = {}
    for k, v in d.items():
        v = np.atleast_1d(np.asarray(v, np.float64))
        if v.size == 1:
            out[k] = float(v[0])
        else:
            out.update({f"{k}[{i}]": float(x) for i, x in enumerate(v)})
    return out


def worst_leaf(prog, ref, keep=None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Returns (gap, leaf)."""
    p, r = _units(prog), _units(ref)
    if set(p) != set(r):
        return float("inf"), f"leaves differ: {sorted(set(p) ^ set(r))[:4]}"
    names = [k for k in r if keep is None or k in keep]
    med = float(np.median([r[k] for k in names])) or 1e-30
    gaps = {k: abs(p[k] - r[k]) / max(r[k], med) for k in names}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def compare(prog, ref):
    """Every number compared, by name. Limits live in the workload's file."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"]), 1):
        out[f"loss{i}_gap"] = abs(a - b) / abs(b)
    out["gnorm1_gap"] = abs(prog["grad_norm"][0] - ref["grad_norm"][0]) / abs(
        ref["grad_norm"][0])
    out["weights_gap"], _ = worst_leaf(
        prog["weight_leaf_norms"], ref["weight_leaf_norms"])
    out["grad_leaf_gap"], _ = worst_leaf(
        prog["grad_leaf_norms"], ref["grad_leaf_norms"])
    # leaves whose gradient is nought to rounding in the reference move under
    # Adam by round-off alone: left out by a rule on the reference's gradient
    g = _units(ref["grad_leaf_norms"])
    med = float(np.median(list(g.values())))
    live = {k for k, v in g.items() if v >= 1e-3 * med}
    out["change_leaf_gap"], _ = worst_leaf(
        prog["change_leaf_norms"], ref["change_leaf_norms"], keep=live)
    return out


def readback(sink, config, state, jax):
    """The window's last save, read back through the engine's restore, against
    the state the trainer held when it saved (no step ran since)."""
    import jax.numpy as jnp
    from pyrecover_tpu.checkpoint.sharded import load_ckpt_sharded

    saved = [r for _, r in sink.records if r.get("event") == "ckpt_saved"]
    if not saved or saved[-1]["step"] != sink.step_close:
        return {"readback_mismatch": float("inf")}
    path = os.path.join(
        config.checkpoint_dir, config.experiment_name, saved[-1]["path"])
    restored, _, meta = load_ckpt_sharded(path, state)
    differ = jax.jit(lambda a, b: jnp.sum(a != b))
    bad = 0
    held = jax.tree_util.tree_leaves(state)
    back = jax.tree_util.tree_leaves(restored)
    if len(held) != len(back):
        return {"readback_mismatch": float("inf")}
    for a, b in zip(held, back):
        if a.shape != b.shape or a.dtype != b.dtype:
            return {"readback_mismatch": float("inf")}
        bad += int(differ(a, b))
    if int(meta.get("step", -1)) != int(sink.step_close):
        bad += 1
    return {"readback_mismatch": float(bad)}
