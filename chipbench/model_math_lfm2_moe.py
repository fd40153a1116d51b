"""Operations and bytes an LFM2-MoE configuration (gated short
convolutions beside grouped-query attention, routed experts all held
here) needs, from its shapes and the program's counters
(``model_math.py``'s rules: what the algorithm requires, whatever
implements it; a multiply-add is two operations; the head only on the
rows that sample).  The routed experts' operations follow the
row-expert pairs that were DISPATCHED (the engine's ``moe_pairs``), not
an expectation of the router."""


def head_dim(sizes):
    return sizes.get("head_dim") \
        or sizes["hidden_size"] // sizes["num_attention_heads"]


def layer_kinds(sizes):
    """(convolution layers, attention layers, dense layers, expert
    layers) of the configuration as it is run."""
    kinds = sizes["layer_types"]
    conv = sum(k == "conv" for k in kinds)
    dense = sizes["num_dense_layers"]
    return conv, len(kinds) - conv, dense, len(kinds) - dense


def conv_operator_params(sizes):
    """A gated short convolution: in_proj (D, 3D), out_proj (D, D) and
    the depthwise taps."""
    D = sizes["hidden_size"]
    return D * 3 * D + D * D + sizes["conv_L_cache"] * D


def attention_operator_params(sizes):
    """q, k, v, o and the two per-head norm gains."""
    D, dh = sizes["hidden_size"], head_dim(sizes)
    H, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return D * H * dh + 2 * D * Hkv * dh + H * dh * D + 2 * dh


def dense_ffn_params(sizes):
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def expert_matmul_params(sizes):
    """One routed expert's three matrices."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def router_params(sizes):
    """The router's matrix and the selection bias."""
    return (sizes["hidden_size"] + 1) * sizes["num_experts"]


def embedding_params(sizes):
    """The embedding, which is the head too."""
    return sizes["hidden_size"] * sizes["vocab_size"]


def total_params(sizes):
    """Every parameter the chip holds but the norms' gains over the
    hidden size (2 a layer and 1: 51 K)."""
    conv, attn, dense, moe = layer_kinds(sizes)
    return conv * conv_operator_params(sizes) \
        + attn * attention_operator_params(sizes) \
        + dense * dense_ffn_params(sizes) \
        + moe * (sizes["num_experts"] * expert_matmul_params(sizes)
                 + router_params(sizes)) \
        + embedding_params(sizes)


def row_matmul_params(sizes):
    """Weights every row passes through, all layers, outside the routed
    experts: each layer's operator (the taps are a multiply-add a
    channel each), the dense SwiGLU in the leading layers, the router
    in the expert layers."""
    conv, attn, dense, moe = layer_kinds(sizes)
    D = sizes["hidden_size"]
    return conv * conv_operator_params(sizes) \
        + attn * (attention_operator_params(sizes) - 2 * head_dim(sizes)) \
        + dense * dense_ffn_params(sizes) \
        + moe * D * sizes["num_experts"]


def attention_flops(sizes, context):
    """One query row against ``context`` cached tokens, all query heads,
    ONE attention layer: q.k and p.v, each 2 x context x heads x head
    size."""
    return 4 * context * sizes["num_attention_heads"] * head_dim(sizes)


def serve_flops(sizes, rows, context_sum, sampled, moe_pairs):
    """Forward of ``rows`` token rows whose causal contexts add up to
    ``context_sum`` cached tokens (read by the attention layers alone),
    ``sampled`` of them followed by the head, with ``moe_pairs``
    row-expert pairs (summed over the layers, as the engine counts
    them)."""
    attn = layer_kinds(sizes)[1]
    return float(2 * row_matmul_params(sizes) * rows
                 + attn * attention_flops(sizes, 1) * context_sum
                 + 2 * expert_matmul_params(sizes) * moe_pairs
                 + 2 * embedding_params(sizes) * sampled)


def expert_bytes(sizes, itemsize=2):
    """One routed expert's weights."""
    return expert_matmul_params(sizes) * itemsize


def page_bytes(sizes, itemsize=2):
    """One page of ONE attention layer: ``page_size`` tokens' keys and
    values of every key/value head."""
    return sizes["engine"]["page_size"] * sizes["num_key_value_heads"] \
        * 2 * head_dim(sizes) * itemsize


def kv_read_bytes(sizes, pages, itemsize=2):
    """Bytes the attention of ``pages`` row-pages (the engine's
    ``kv_pages_read``: each row's own pages, one layer) has to read, all
    attention layers."""
    return pages * page_bytes(sizes, itemsize) * layer_kinds(sizes)[1]


def window_bytes(sizes, itemsize=2):
    """One slot's windows, all convolution layers: the last
    ``conv_L_cache - 1`` gated rows of each."""
    return layer_kinds(sizes)[0] * (sizes["conv_L_cache"] - 1) \
        * sizes["hidden_size"] * itemsize
