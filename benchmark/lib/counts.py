"""Operations and bytes the algorithms require, from shapes alone.

Kept with the benchmark so that no later PR can move a utilization by editing
the arithmetic. ``cfg`` is a configuration file's dict (Hugging Face keys).

Conventions, stated once:

* A product of an (m, k) by a (k, n) matrix is 2 m k n operations.
* Training is forward plus backward = 3 x the forward products. Operations a
  rematerialised forward repeats are NOT counted: a utilization is of the
  operations the mathematics requires.
* Causal attention requires only the pairs (query i, key j <= i): S (S + 1) / 2
  of them per head, not S^2. Utilizations here are therefore lower than ones
  that count the full square (the trainer's own meter does, 12 L H hd S).
* A mixture-of-experts layer counts the experts a token is routed to
  (``num_experts_per_tok``), not the experts held, and the router's product.
* The embedding lookup is a gather, not a product; the output head is a
  product and counts. Norms, rotary positions, softmax and the optimizer's
  elementwise work are not counted.
"""


def _dims(cfg):
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim", cfg["hidden_size"] // heads)
    return cfg["hidden_size"], heads, cfg["num_key_value_heads"], hd


def attention_params(cfg):
    d, h, kv, hd = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def expert_params(cfg):
    """One SwiGLU feed-forward: gate, up and down projections."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg):
    """Parameters of one decoder layer as held (all experts, both norms)."""
    d = cfg["hidden_size"]
    e = cfg.get("num_local_experts", 0)
    ffn = e * expert_params(cfg) + d * e if e else expert_params(cfg)
    return attention_params(cfg) + ffn + 2 * d


def layer_matmul_params_active(cfg):
    """Matrix elements one token is multiplied with in one layer."""
    d = cfg["hidden_size"]
    e = cfg.get("num_local_experts", 0)
    if e:
        ffn = cfg["num_experts_per_tok"] * expert_params(cfg) + d * e
    else:
        ffn = expert_params(cfg)
    return attention_params(cfg) + ffn


def total_params(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return cfg["num_hidden_layers"] * layer_params(cfg) + v * d + head + d


def attention_pairs(seq, causal=True):
    return seq * (seq + 1) // 2 if causal else seq * seq


def train_flops_per_token(cfg, seq):
    """Forward + backward operations one trained token requires."""
    d, h, _, hd = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    products = layers * layer_matmul_params_active(cfg) + d * cfg["vocab_size"]
    # scores and weighted values: 2 products of hd per (query, key) pair and head
    attn = layers * h * 2 * 2 * hd * attention_pairs(seq) / seq
    return 3 * (2 * products + attn)


# --- the flash-attention kernel's three calls -------------------------------
# per (query, key) pair and query head, in products of length hd:
#   forward:  scores, weighted values                                  = 2
#   dq call:  scores again, dP = dO V^T, dQ = dS K                     = 3
#   dkv call: scores again, dP = dO V^T, dV = P^T dO, dK = dS^T Q      = 4
FLASH_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_flops(call, batch, heads, seq, head_dim, causal=True):
    return (FLASH_PRODUCTS[call] * 2 * head_dim * attention_pairs(seq, causal)
            * heads * batch)


def flash_bytes(call, batch, heads, kv_heads, seq, head_dim, itemsize=2):
    """Least HBM traffic of one call: every operand read once, every result
    written once (log-sum-exp and delta rows in float32)."""
    q = batch * heads * seq * head_dim * itemsize
    kv = batch * kv_heads * seq * head_dim * itemsize
    row = batch * heads * seq * 4
    if call == "fwd":
        return q + 2 * kv + q + row
    if call == "dq":
        return q + 2 * kv + q + 2 * row + q
    if call == "dkv":
        return q + 2 * kv + q + 2 * row + 2 * kv
    raise KeyError(call)


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds, which bound binds) on a chip with these peaks."""
    t_ops = flops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
