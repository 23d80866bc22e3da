"""Operations and bytes a hybrid stack of Mamba-1 and attention layers
requires, from shapes alone (``cfg``: a configuration file's dict with the
Jamba keys ``attn_layer_period`` / ``attn_layer_offset`` / ``mamba_*``).

Conventions of ``benchmark/lib/counts.py``, stated there: a product is
2 m k n, training is 3 x the forward operations, nothing a rematerialised
forward repeats is counted, causal attention is the half square, the
embedding lookup is a gather. The head is TIED to the embedding: its product
counts (once), its parameters are held once. Besides:

* the selective recurrence counts 6 operations a (channel, state) pair and
  token forward (the step's product with A, the exponential, the input's two
  products, the state's multiply-add, the read-out's multiply-add counted as
  one each) and the depthwise convolution 2 k a channel;
* the norms, softplus, silu gates and the optimizer are not counted.

The scan's least bytes are what the recurrence itself must move whatever
implements it: per token and Mamba layer u, B, C at the configuration's
compute type (2 B), the step Dt in float32 (the equations state it so), y out
at 2 B; the backward reads those and dy again and writes du, dDt, dB, dC. The
gate ``y * silu(z)`` is NOT the recurrence's: it runs in the fusions round the
timed operations (``benchmark/lib/ssm_trace.py``), so z and dz are not counted
against them. A, D and the chunk-boundary states are small and not counted.
"""

from benchmark.lib import counts

RECURRENCE_OPS = 6  # forward, a (channel, state) pair and token


def attn_layers(cfg):
    return cfg["num_hidden_layers"] // cfg["attn_layer_period"]


def mamba_layers(cfg):
    return cfg["num_hidden_layers"] - attn_layers(cfg)


def d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mixer_matmul_params(cfg):
    """in_proj, x_proj, dt_proj, out_proj of one Mamba mixer."""
    d, di = cfg["hidden_size"], d_inner(cfg)
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def mixer_params(cfg):
    """A Mamba mixer as held: the four matrices, the convolution and its
    bias, dt_proj's bias, A_log, D and the three inner norms."""
    di, n, r = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    small = di * cfg["mamba_d_conv"] + di + di + di * n + di + r + 2 * n
    return mixer_matmul_params(cfg) + small


def mamba_layer_params(cfg):
    return mixer_params(cfg) + counts.expert_params(cfg) + 2 * cfg["hidden_size"]


def attn_layer_params(cfg):
    return (counts.attention_params(cfg) + counts.expert_params(cfg)
            + 2 * cfg["hidden_size"])


def total_params(cfg):
    """Held: the layers, the tied embedding once, the final norm."""
    d = cfg["hidden_size"]
    return (mamba_layers(cfg) * mamba_layer_params(cfg)
            + attn_layers(cfg) * attn_layer_params(cfg)
            + cfg["vocab_size"] * d + d)


def scan_flops_per_token(cfg):
    """Forward operations of ONE Mamba layer's recurrence, a token."""
    return RECURRENCE_OPS * d_inner(cfg) * cfg["mamba_d_state"]


def train_flops_per_token(cfg, seq):
    """Forward + backward operations one trained token requires."""
    d, h, _, hd = counts._dims(cfg)
    ffn = counts.expert_params(cfg)
    products = (mamba_layers(cfg) * (mixer_matmul_params(cfg) + ffn)
                + attn_layers(cfg) * (counts.attention_params(cfg) + ffn)
                + d * cfg["vocab_size"])
    attn = attn_layers(cfg) * h * 2 * 2 * hd * counts.attention_pairs(seq) / seq
    scan = mamba_layers(cfg) * (
        scan_flops_per_token(cfg) + 2 * cfg["mamba_d_conv"] * d_inner(cfg))
    return 3 * (2 * products + attn + scan)


def scan_share(cfg, seq):
    """The mixers' products, convolution and recurrence: their share of a
    token's operations."""
    mix = mamba_layers(cfg) * (
        2 * mixer_matmul_params(cfg) + scan_flops_per_token(cfg)
        + 2 * cfg["mamba_d_conv"] * d_inner(cfg))
    return 3 * mix / train_flops_per_token(cfg, seq)


def scan_bytes(call, tokens, cfg, itemsize=2):
    """Least HBM traffic of one Mamba layer's recurrence over ``tokens``
    tokens: ``fwd`` (also the forward a rematerialised layer repeats) or
    ``bwd``."""
    di, n = d_inner(cfg), cfg["mamba_d_state"]
    ins = di * (itemsize + 4) + 2 * n * itemsize  # u, Dt; B, C
    if call == "fwd":
        return tokens * (ins + di * itemsize)  # + y
    if call == "bwd":
        return tokens * (ins + di * itemsize + ins)  # + dy; their cotangents
    raise KeyError(call)


def scan_flops(call, tokens, cfg):
    """Operations of one layer's recurrence: the backward is twice the
    forward (the adjoint of every multiply-add), its recomputation inside a
    chunk not counted."""
    fwd = tokens * scan_flops_per_token(cfg)
    return {"fwd": fwd, "bwd": 2 * fwd}[call]
