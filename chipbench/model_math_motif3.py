"""Operations and bytes a Motif-3 configuration (grouped differential
latent attention in sliding-window layers that keep a latent ring and
full layers that keep latent pages, a four-stream mHC residual, PolyNorm
MLPs, one rank's share of the routed experts and a shared expert) needs,
from its shapes and the program's counters (``model_math.py``'s rules:
what the algorithm requires, whatever implements it; a multiply-add is
two operations; the head only on the rows that sample).  The attention
is counted in its served, absorbed form, as the DeepSeek-V3 cell's is.
The routed experts' operations follow the row-expert pairs that were
DISPATCHED to held experts (the engine's ``moe_pairs``); a sliding
layer's attention reads at most the window, a full layer's the row's
whole context."""


def windows(sizes):
    """Each layer's window, 0 for a full layer: layer ``i`` slides where
    ``i mod sliding_window_period`` is not the period's last."""
    period = sizes["sliding_window_period"]
    return [sizes["sliding_window"] if sizes["use_sliding_window"]
            and i % period != period - 1 else 0
            for i in range(sizes["num_hidden_layers"])]


def window(sizes):
    return max(windows(sizes))


def layer_kinds(sizes):
    """(sliding layers, full layers, dense layers, expert layers) of the
    configuration as it is run."""
    sliding = sum(w > 0 for w in windows(sizes))
    L = sizes["num_hidden_layers"]
    dense = sizes["n_dense_first_layers"]
    return sliding, L - sliding, dense, L - dense


def heads(sizes):
    """(query heads, key/value groups, signal heads)."""
    H, G = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return H, G, H - sizes["num_noise_heads"]


def attention_operator_params(sizes):
    """q_a, q_b, kv_a, kv_b, the signal heads' lambda, the output gate
    and o (the norms' gains left out)."""
    D, (H, G, Hs) = sizes["hidden_size"], heads(sizes)
    Rq, R = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    rope, dv = sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    nope = sizes["head_dim"] - rope
    return D * Rq + Rq * H * (nope + rope) + D * (R + rope) \
        + R * G * (nope + dv) + D * Hs + 2 * D * Hs * dv


def mhc_params(sizes):
    """One layer's two mHC maps, (n D) x n (n + 2) each (their scales
    and biases left out)."""
    n = sizes["mhc_expansion_rate"]
    return 2 * n * sizes["hidden_size"] * n * (n + 2)


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
    """An expert layer's parameters on this chip but its attention and
    mHC maps: the held experts, the shared expert and the router."""
    return sizes["num_experts"] * expert_matmul_params(sizes) \
        + shared_expert_params(sizes) + router_params(sizes)


def total_params(sizes):
    """Every parameter the chip holds but the norms' gains, the mHC
    scales and biases and the PolyNorms' four numbers."""
    _, _, dense, moe = layer_kinds(sizes)
    return sizes["num_hidden_layers"] * (attention_operator_params(sizes)
                                         + mhc_params(sizes)) \
        + dense * dense_ffn_params(sizes) \
        + moe * expert_layer_params(sizes) + 2 * embedding_params(sizes)


def attention_row_params(sizes):
    """Weights a row passes through in one layer's served attention:
    q_a, q_b, kv_a, W_kvb's key part against each head's query (``rank x
    nope`` a head), its value part against each signal head's read-back
    (``rank x v``), lambda, the gate and o."""
    D, (H, _, Hs) = sizes["hidden_size"], heads(sizes)
    Rq, R = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    rope, dv = sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    nope = sizes["head_dim"] - rope
    return D * Rq + Rq * H * (nope + rope) + D * (R + rope) \
        + R * (H * nope + Hs * dv) + D * Hs + 2 * D * Hs * dv


def row_matmul_params(sizes):
    """Weights every row passes through, all layers, outside the routed
    experts: each layer's attention and mHC maps, the dense MLP in the
    dense layers, the shared expert and the router in the expert
    layers."""
    _, _, dense, moe = layer_kinds(sizes)
    return sizes["num_hidden_layers"] * (attention_row_params(sizes)
                                         + mhc_params(sizes)) \
        + dense * dense_ffn_params(sizes) \
        + moe * (shared_expert_params(sizes)
                 + sizes["hidden_size"] * router_width(sizes))


def polynorm_flops(width):
    """PolyNorm over one row of ``width``, times ``up``: z^2 and z^3, three
    mean squares (a multiply-add an element each), three scalings, three
    weights, the sum and its scale and bias, the product with up."""
    return 18 * width


def mhc_flops(sizes):
    """One branch's mix on one row but the map's matmul: the stream's
    RMS (n D multiply-adds), H_pre X (n D), H_res X (n^2 D) and
    H_post^T F (n D); Sinkhorn's n^2 entries are left out."""
    n, D = sizes["mhc_expansion_rate"], sizes["hidden_size"]
    return 2 * (3 * n * D + n * n * D)


def row_elementwise_flops(sizes):
    """All layers' mHC mixes (two branches a layer) and the dense
    layers' and shared experts' PolyNorms on one row."""
    _, _, dense, moe = layer_kinds(sizes)
    return sizes["num_hidden_layers"] * 2 * mhc_flops(sizes) \
        + dense * polynorm_flops(sizes["intermediate_size"]) \
        + moe * sizes["num_shared_experts"] \
        * polynorm_flops(sizes["moe_intermediate_size"])


def attention_flops(sizes, context):
    """One query row against ``context`` cached latent rows, all heads,
    ONE attention layer: scores over rank + rope lanes, the read-back
    over rank."""
    H = sizes["num_attention_heads"]
    R, rope = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    return 2 * context * H * ((R + rope) + R)


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
    return float((2 * row_matmul_params(sizes)
                  + row_elementwise_flops(sizes)) * rows
                 + full * attention_flops(sizes, 1) * context_sum
                 + sliding * attention_flops(sizes, 1) * window_context_sum
                 + (2 * expert_matmul_params(sizes)
                    + polynorm_flops(sizes["moe_intermediate_size"]))
                 * moe_pairs
                 + 2 * embedding_params(sizes) * sampled)


def latent_row_bytes(sizes, itemsize=2):
    """One cached token of one layer: ``[c_kv | k_pe]``, the REQUIRED
    ``kv_lora_rank + qk_rope_head_dim`` values (pages and rings pad the
    row to whole lane tiles; the padding is not the algorithm's)."""
    return (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]) * itemsize


def stored_row_bytes(sizes, itemsize=2):
    """The row as a page or a ring holds it: padded to 128-lane tiles."""
    lanes = sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]
    return -(-lanes // 128) * 128 * itemsize


def latent_walk_bytes(sizes, pages, itemsize=2):
    """Latent rows the walk of ``pages`` row-pages (the engine's
    ``kv_pages_read``: each row's own pages, one layer) has to read,
    every layer that keeps pages (the full layers alone)."""
    return pages * sizes["engine"]["page_size"] \
        * latent_row_bytes(sizes, itemsize) * layer_kinds(sizes)[1]


def ring_read_bytes(sizes, positions, itemsize=2):
    """Latent rows the sliding layers' attention had to read: the
    engine's ``win_positions``, already summed over those layers."""
    return positions * latent_row_bytes(sizes, itemsize)


def pool_bytes(sizes, itemsize=2):
    """(the full layers' pages, the sliding layers' rings) the engine
    holds: every page of every slot and the scratch page, every slot's
    ring and the scratch slot's."""
    e = sizes["engine"]
    sliding, full, _, _ = layer_kinds(sizes)
    row = stored_row_bytes(sizes, itemsize)
    pages = (e["num_slots"] * e["pages_per_slot"] + 1) * e["page_size"]
    return full * pages * row, \
        sliding * (e["num_slots"] + 1) * window(sizes) * row
