"""Paged KV cache: fixed-size blocks in a preallocated pool.

The lockstep decoder (``models/decode.py``) gives every sequence one
contiguous ``max_len`` cache slice for its whole lifetime — a finished
sequence keeps holding memory until the slowest one in its batch ends,
and a new request cannot start until the whole batch drains. This module
is the serving-side replacement: KV storage is a single preallocated
pool of fixed-size blocks (``block_size`` token positions each), a
host-side free list hands blocks to sequences as they are admitted, and
a per-sequence **block table** maps logical position ``p`` to physical
block ``table[p // block_size]``. A finished sequence releases its
blocks mid-flight; the next queued request claims them without any
reallocation or recompilation — the pool arrays never change shape.

Block 0 is the **trash block**: it is never handed out by the free list,
every unassigned block-table slot points at it, and out-of-range or
padding writes are routed into it. Attention masks make its contents
unobservable (a key is only attended at ``kpos <= qpos``, and every real
position is written before any query reaches it), so clamping to block 0
turns every edge case — prefill padding past the prompt, inactive decode
slots — into a harmless write instead of a bounds error.

int8 mode (``kv_mode="int8"``) stores the pool as int8 payloads plus one
f32 scale per ``head_dim`` elements — the exact symmetric per-block
quantizer the gradient collectives use (``parallel/collectives.py:
block_quantize_int8`` with ``block=head_dim``, i.e. one scale per head
per token). Per token per layer the KV bytes drop from ``2·Hkv·hd·4``
(fp32) to ``2·Hkv·(hd + 4)`` — ~3.8× more resident sequences in the
same pool budget at ``hd=64`` (:func:`resident_sequences` is the
accounting the capacity tests pin). Quantization happens once on append;
the attention gather dequantizes blocks on the fly.
"""

# concur: disable-file=unguarded-shared-state -- single-consumer protocol:
# the free list/_held map are touched only by ServingEngine._pump, which
# is pinned to exactly one scheduler thread at a time (runtime-enforced;
# see serving/engine.py).

import jax.numpy as jnp
import numpy as np

from pyrecover_tpu.utils.dtypes import resolve_dtype

KV_MODES = ("native", "int8")
TRASH_BLOCK = 0


def kv_token_bytes(config, mode, dtype=None):
    """Bytes of KV storage one token position occupies across ALL layers.

    ``native`` prices the pool's element dtype (the model's compute
    dtype by default); ``int8`` prices 1 byte per element plus one f32
    scale per head per token — the ``block=head_dim`` quantizer layout.
    """
    cfg = config
    per_head = cfg.head_dim
    heads = cfg.n_kv_heads
    if mode == "int8":
        per_token = 2 * heads * (per_head * 1 + 4)  # payload + f32 scale
    else:
        elem = np.dtype(resolve_dtype(dtype or cfg.compute_dtype)).itemsize
        per_token = 2 * heads * per_head * elem
    return per_token * cfg.n_layers


def kv_block_bytes(config, block_size, mode, dtype=None):
    """Bytes one pool block (``block_size`` token positions) occupies."""
    return kv_token_bytes(config, mode, dtype) * int(block_size)


def blocks_for(seq_len, block_size):
    """Blocks a sequence of ``seq_len`` positions needs (ceil)."""
    return -(-int(seq_len) // int(block_size))


def resident_sequences(budget_bytes, config, block_size, mode, seq_len,
                       dtype=None):
    """How many ``seq_len``-position sequences a pool of ``budget_bytes``
    holds at once — the capacity accounting the int8-vs-fp32 ratio test
    pins (the +1 reserves the trash block)."""
    per_block = kv_block_bytes(config, block_size, mode, dtype)
    n_blocks = int(budget_bytes) // per_block
    usable = max(n_blocks - 1, 0)  # block 0 is the trash block
    return usable // blocks_for(seq_len, block_size)


class BlockPool:
    """Preallocated paged KV pool + host-side free list.

    Device arrays (one pytree, threaded through the jitted serving step
    and donated back):

      * ``native``: ``{"k", "v"}`` each ``(L, n_blocks, block_size,
        Hkv, head_dim)`` in the pool dtype;
      * ``int8``: ``{"k", "v"}`` int8 of the same shape plus
        ``{"k_scale", "v_scale"}`` f32 ``(L, n_blocks, block_size, Hkv)``
        — one scale per head per token position.

    Host-side accounting (``alloc``/``release``/``free_blocks``) is
    plain-list bookkeeping with no lock: the serving engine mutates it
    from exactly one scheduler thread (the single-consumer protocol the
    engine enforces at runtime; see ``ServingEngine._pump``).
    """

    def __init__(self, config, n_blocks, block_size, *,  # jaxlint: host-only
                 kv_mode="native", dtype=None):
        if kv_mode not in KV_MODES:
            raise ValueError(
                f"kv_mode must be one of {KV_MODES}, got {kv_mode!r}"
            )
        if n_blocks < 2:
            raise ValueError(
                f"the pool needs >= 2 blocks (block 0 is reserved as the "
                f"trash block), got {n_blocks}"
            )
        from pyrecover_tpu.models.llama import refuse_hybrid, refuse_looped

        # a block's footprint is priced per layer (kv_token_bytes) and the
        # paged sweep runs the layers once; ServingEngine, and with it the
        # fleet's replicas, build their pool here, so they refuse here too
        refuse_looped(config, "the paged serving engine (BlockPool)")
        refuse_hybrid(config, "the paged serving engine (BlockPool)")
        if config.tie_embeddings or not config.rope:
            raise ValueError(
                "the paged serving engine (BlockPool) sweeps layers with "
                "rotary positions and reads an untied head: a model with "
                "tie_embeddings or without rope is not served by it"
            )
        self.config = config
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self.kv_mode = kv_mode
        self.dtype = resolve_dtype(dtype or config.compute_dtype)
        shape = (
            config.n_layers, self.n_blocks, self.block_size,
            config.n_kv_heads, config.head_dim,
        )
        if kv_mode == "int8":
            self.arrays = {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.ones(shape[:-1], jnp.float32),
                "v_scale": jnp.ones(shape[:-1], jnp.float32),
            }
        else:
            self.arrays = {
                "k": jnp.zeros(shape, self.dtype),
                "v": jnp.zeros(shape, self.dtype),
            }
        # LIFO free list over blocks 1..n-1; block 0 stays the trash sink
        self._free = list(range(self.n_blocks - 1, TRASH_BLOCK, -1))
        self._held = {}  # seq key -> list of block ids (leak accounting)

    @classmethod
    def from_budget(cls, config, budget_bytes, block_size, *,  # jaxlint: host-only
                    kv_mode="native", dtype=None):
        """Size the pool to a byte budget (the serving analogue of the
        SC05 HBM table): as many blocks as ``budget_bytes`` buys."""
        per_block = kv_block_bytes(config, block_size, kv_mode, dtype)
        return cls(
            config, max(int(budget_bytes) // per_block, 2), block_size,
            kv_mode=kv_mode, dtype=dtype,
        )

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def usable_blocks(self):
        """Total allocatable blocks (pool minus the trash block)."""
        return self.n_blocks - 1

    @property
    def held_blocks(self):
        return sum(len(v) for v in self._held.values())

    def alloc(self, key, n):  # jaxlint: host-only
        """Take ``n`` blocks for sequence ``key``; None when the free
        list cannot cover the whole request (no partial grants — the
        admission gate either admits a sequence fully or leaves it
        queued)."""
        n = int(n)
        if n <= 0:
            raise ValueError(f"alloc needs a positive block count, got {n}")
        if key in self._held:
            raise ValueError(f"sequence {key!r} already holds blocks")
        if n > len(self._free):
            return None
        # grant atomically: take the tail slice, then commit both sides.
        # A per-block pop loop would leave blocks stranded off the free
        # list if anything raised mid-grant (a hostile list subclass, a
        # KeyboardInterrupt) — "no partial grants" has to hold on the
        # exception path too, not just the None path.
        got = self._free[-n:][::-1]  # same order the old pop loop granted
        del self._free[-n:]
        self._held[key] = got
        return got

    def release(self, key):  # jaxlint: host-only
        """Return sequence ``key``'s blocks to the free list (mid-flight:
        the very next admission can claim them)."""
        blocks = self._held.pop(key)
        self._free.extend(blocks)
        return len(blocks)

    def check_drained(self):  # jaxlint: host-only
        """Raise unless every non-trash block is back on the free list —
        the zero-leak accounting the serving smoke gate asserts after a
        full drain."""
        if self._held or len(self._free) != self.usable_blocks:
            raise RuntimeError(
                f"KV block leak: {self.held_blocks} blocks still held by "
                f"{sorted(self._held)} and {len(self._free)} of "
                f"{self.usable_blocks} free"
            )

    def table_width(self, max_model_len):
        """Block-table width covering ``max_model_len`` positions."""
        return blocks_for(max_model_len, self.block_size)

    def block_bytes(self):
        return kv_block_bytes(
            self.config, self.block_size, self.kv_mode, self.dtype
        )

    def pool_bytes(self):
        return self.block_bytes() * self.n_blocks


def make_block_table(width, block_ids=None):
    """One sequence's block table row as int32 — unassigned slots point
    at the trash block."""
    row = np.full((int(width),), TRASH_BLOCK, dtype=np.int32)
    if block_ids:
        if len(block_ids) > width:
            raise ValueError(
                f"{len(block_ids)} blocks exceed the table width {width}"
            )
        row[: len(block_ids)] = block_ids
    return row
