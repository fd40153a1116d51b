"""Operations and bytes an EXAONE-MoE configuration (sliding-window
attention layers that keep a ring beside full attention layers that keep
pages; one rank's share of the routed experts and a shared expert) needs,
from its shapes and the program's counters (``model_math.py``'s rules:
what the algorithm requires, whatever implements it; a multiply-add is
two operations; the head only on the rows that sample).  The routed
experts' operations follow the row-expert pairs that were DISPATCHED to
held experts (the engine's ``moe_pairs``), not an expectation of the
router; a sliding layer's attention reads at most the window, a full
layer's the row's whole context."""


def head_dim(sizes):
    return sizes["head_dim"]


def window(sizes):
    """The sliding layers' window (the configuration's largest)."""
    return max(sizes["sliding_windows"])


def layer_kinds(sizes):
    """(sliding layers, full layers, dense layers, expert layers) of the
    configuration as it is run."""
    sliding = sum(w > 0 for w in sizes["sliding_windows"])
    dense = sum(k == "dense" for k in sizes["mlp_layer_types"])
    L = sizes["num_hidden_layers"]
    return sliding, L - sliding, dense, L - dense


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


def shared_expert_params(sizes):
    return expert_matmul_params(sizes) * sizes["num_shared_experts"]


def router_width(sizes):
    return sizes.get("router_width", sizes["num_experts"])


def router_params(sizes):
    """The router's matrix over all experts and the selection bias."""
    return (sizes["hidden_size"] + 1) * router_width(sizes)


def embedding_params(sizes):
    """The embedding's rows held here; the untied head holds as many."""
    return sizes["hidden_size"] * sizes["vocab_size"]


def expert_layer_params(sizes):
    """An expert layer's parameters on this chip but its attention: the
    held experts, the shared expert and the router."""
    return sizes["num_experts"] * expert_matmul_params(sizes) \
        + shared_expert_params(sizes) + router_params(sizes)


def total_params(sizes):
    """Every parameter the chip holds but the norms' gains over the
    hidden size (2 a layer and 1)."""
    _, _, dense, moe = layer_kinds(sizes)
    return sizes["num_hidden_layers"] * attention_operator_params(sizes) \
        + dense * dense_ffn_params(sizes) \
        + moe * expert_layer_params(sizes) + 2 * embedding_params(sizes)


def row_matmul_params(sizes):
    """Weights every row passes through, all layers, outside the routed
    experts: each layer's attention, the dense SwiGLU in the dense
    layers, the shared expert and the router in the expert layers."""
    _, _, dense, moe = layer_kinds(sizes)
    return sizes["num_hidden_layers"] \
        * (attention_operator_params(sizes) - 2 * head_dim(sizes)) \
        + dense * dense_ffn_params(sizes) \
        + moe * (shared_expert_params(sizes)
                 + sizes["hidden_size"] * router_width(sizes))


def attention_flops(sizes, context):
    """One query row against ``context`` cached tokens, all query heads,
    ONE attention layer: q.k and p.v, each 2 x context x heads x head
    size."""
    return 4 * context * sizes["num_attention_heads"] * head_dim(sizes)


def windowed_prompt_context(prompt, w):
    """The contexts ``0 .. prompt - 1`` of a prompt's rows, each capped
    at the window ``w``, summed: what ``sum(range(prompt))`` is to a full
    layer."""
    n = min(prompt, w + 1)
    return n * (n - 1) // 2 + (prompt - n) * w


def serve_flops(sizes, rows, context_sum, window_context_sum, sampled,
                moe_pairs):
    """Forward of ``rows`` token rows whose causal contexts add up to
    ``context_sum`` cached tokens (what a full layer reads) and, each
    capped at the window, to ``window_context_sum`` (what a sliding layer
    reads), ``sampled`` of them followed by the head, with ``moe_pairs``
    row-expert pairs on held experts (summed over the layers, as the
    engine counts them)."""
    sliding, full, _, _ = layer_kinds(sizes)
    return float(2 * row_matmul_params(sizes) * rows
                 + full * attention_flops(sizes, 1) * context_sum
                 + sliding * attention_flops(sizes, 1) * window_context_sum
                 + 2 * expert_matmul_params(sizes) * moe_pairs
                 + 2 * embedding_params(sizes) * sampled)


def expert_bytes(sizes, itemsize=2):
    """One routed expert's weights."""
    return expert_matmul_params(sizes) * itemsize


def kv_row_bytes(sizes, itemsize=2):
    """One token's ``[k | v]`` row of ONE layer, every key/value head:
    a page's row and a ring's entry alike."""
    return sizes["num_key_value_heads"] * 2 * head_dim(sizes) * itemsize


def page_bytes(sizes, itemsize=2):
    """One page of ONE full layer."""
    return sizes["engine"]["page_size"] * kv_row_bytes(sizes, itemsize)


def kv_read_bytes(sizes, pages, itemsize=2):
    """Bytes the attention of ``pages`` row-pages (the engine's
    ``kv_pages_read``: each row's own pages, one layer) has to read, all
    full layers."""
    return pages * page_bytes(sizes, itemsize) * layer_kinds(sizes)[1]


def ring_bytes(sizes, itemsize=2):
    """One slot's rings, all sliding layers: ``window`` entries each."""
    return layer_kinds(sizes)[0] * window(sizes) \
        * kv_row_bytes(sizes, itemsize)
