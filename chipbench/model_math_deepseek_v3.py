"""Operations and bytes a DeepSeek-V3 (latent attention + routed experts)
share needs, from its shapes and the program's counters (``model_math.py``'s
rules: what the algorithm requires, whatever implements it; a
multiply-add is two operations; the head only on the rows that sample).
The routed experts' operations follow the row-expert pairs that were
DISPATCHED to experts held here (the engine's ``moe_pairs``), not an
expectation of the router."""


def attention_matmul_params(sizes):
    """Weights of one layer's attention projections: q_a, q_b, kv_a,
    kv_b (its key part applied to the row's queries and its value part
    to the row's read-back in the served, absorbed form: once a row
    either way) and o."""
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    Rq, R = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    return D * Rq + Rq * H * (nope + rope) + D * (R + rope) \
        + R * H * (nope + dv) + H * dv * D


def expert_matmul_params(sizes):
    """One routed expert's three matrices."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def row_matmul_params(sizes):
    """Weights every row passes through, all layers, outside the routed
    experts: attention everywhere; the dense SwiGLU in the leading
    layers; the shared expert and the router (over all
    ``router_width`` experts) in the expert layers."""
    D = sizes["hidden_size"]
    L, dense = sizes["num_hidden_layers"], sizes["first_k_dense_replace"]
    width = sizes.get("router_width", sizes["n_routed_experts"])
    return L * attention_matmul_params(sizes) \
        + dense * 3 * D * sizes["intermediate_size"] \
        + (L - dense) * (sizes["n_shared_experts"]
                         * expert_matmul_params(sizes) + D * width)


def head_matmul_params(sizes):
    """The untied output projection over the vocabulary held here."""
    return sizes["hidden_size"] * sizes["vocab_size"]


def attention_flops(sizes, context):
    """One query row against ``context`` cached latent rows, all heads,
    one layer: scores over rank + rope lanes, the read-back over rank."""
    H = sizes["num_attention_heads"]
    R, rope = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    return 2 * context * H * ((R + rope) + R)


def serve_flops(sizes, rows, context_sum, sampled, moe_pairs):
    """Forward of ``rows`` token rows whose causal contexts add up to
    ``context_sum`` cached tokens, ``sampled`` of them followed by the
    head, with ``moe_pairs`` row-expert pairs on held experts (summed
    over the layers, as the engine counts them)."""
    return float(2 * row_matmul_params(sizes) * rows
                 + sizes["num_hidden_layers"]
                 * attention_flops(sizes, 1) * context_sum
                 + 2 * expert_matmul_params(sizes) * moe_pairs
                 + 2 * head_matmul_params(sizes) * sampled)


def latent_row_bytes(sizes, itemsize=2):
    """One cached token of one layer: ``[c_kv | k_pe]``, the REQUIRED
    ``kv_lora_rank + qk_rope_head_dim`` values (the pool pads the row to
    whole lane tiles; the padding is not the algorithm's)."""
    return (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]) * itemsize


def latent_read_bytes(sizes, pages, page_size, itemsize=2):
    """Bytes the attention of ``pages`` row-pages (the engine's
    ``kv_pages_read``: each row's own pages, one layer) has to read, all
    layers."""
    return pages * page_size * latent_row_bytes(sizes, itemsize) \
        * sizes["num_hidden_layers"]


def expert_bytes(sizes, itemsize=2):
    """One routed expert's weights."""
    return expert_matmul_params(sizes) * itemsize
