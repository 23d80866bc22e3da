"""The timed path, broken underneath on purpose.

Each fault wraps the step function the trainer drives, below the harness's own
wrapper (which still records the rows as they were fed). The comparison has to
fail every one of them; the tests plant them at a toy width, the calibration
(``benchmark/calibrate.py``) reads them on the chip at the cell's own size.
"""


def frozen_state(fn, chips):
    """A step that returns its state unchanged (the metrics are real)."""
    import jax
    import jax.numpy as jnp

    def step(state, batch):
        keep = jax.tree_util.tree_map(jnp.copy, state)
        _, metrics = fn(state, batch)
        return keep, metrics

    return step


def half_batch(fn, chips):
    """Half of the batch left out, the mean taken over the rest."""
    import jax.numpy as jnp

    def step(state, batch):
        half = batch["inputs"].shape[0] // 2
        twice = {k: jnp.concatenate([v[:half], v[:half]]).astype(v.dtype)
                 for k, v in batch.items()}
        twice = {k: _like(v, batch[k]) for k, v in twice.items()}
        return fn(state, twice)

    return step


def no_exchange(fn, chips):
    """The exchange between chips left out: every chip's gradient is the first
    chip's own (its rows stand in for everyone's), as if nothing were summed
    across the chips."""
    import jax.numpy as jnp

    def step(state, batch):
        own = batch["inputs"].shape[0] // max(chips, 1)
        tiled = {k: _like(jnp.concatenate([v[:own]] * max(chips, 1)), v)
                 for k, v in batch.items()}
        return fn(state, tiled)

    return step


def label_shift(fn, chips):
    """A token altered where it is produced: every label moved one place."""
    import jax.numpy as jnp

    def step(state, batch):
        moved = dict(batch)
        moved["labels"] = _like(jnp.roll(batch["labels"], 1, axis=1),
                                batch["labels"])
        return fn(state, moved)

    return step


def _like(value, ref):
    import jax

    return jax.device_put(value, ref.sharding)


FAULTS = {f.__name__: f for f in
          (frozen_state, half_batch, no_exchange, label_shift)}
