"""Operations a looped stack requires, from shapes alone.

``benchmark/lib/counts.py`` counts ``num_hidden_layers`` once and the head
once; a looped model (``total_ut_steps`` = T passes over the same L layers, the
head read after every pass) does T L layer products and attention sweeps and T
head products a token. Same conventions as that file, stated there: a product
is 2 m k n, training is 3 x the forward products, nothing a rematerialised
forward repeats is counted, causal attention is the half square, the
embedding lookup is a gather. Not counted besides: the norms (four a layer),
the exit gate's (D -> 1) product and the exit distribution, all elementwise
or a vector.
"""

from benchmark.lib import counts


def passes(cfg):
    return cfg["total_ut_steps"]


def layer_passes(cfg):
    return passes(cfg) * cfg["num_hidden_layers"]


def layer_params(cfg):
    """Parameters of one layer as held: the matrices and four norm scales."""
    return counts.layer_matmul_params_active(cfg) + 4 * cfg["hidden_size"]


def total_params(cfg):
    """Held once however often they are used: layers, embedding, head, final
    norm, and the gate's weight and bias."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer_params(cfg) + 2 * v * d + d + d + 1


def train_flops_per_token(cfg, seq):
    """Forward + backward operations one trained token requires."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // h)
    products = (layer_passes(cfg) * counts.layer_matmul_params_active(cfg)
                + passes(cfg) * d * cfg["vocab_size"])
    attn = layer_passes(cfg) * h * 2 * 2 * hd * counts.attention_pairs(seq) / seq
    return 3 * (2 * products + attn)


def head_share(cfg, seq):
    """The T head products' share of a token's operations."""
    head = 3 * 2 * passes(cfg) * cfg["hidden_size"] * cfg["vocab_size"]
    return head / train_flops_per_token(cfg, seq)
