"""Operations and bytes a Falcon-H1 block needs, from its shapes alone
(``model_math.py``'s rules: what the algorithm requires, whatever
implements it; a multiply-add is two operations; the head only on the
rows that sample)."""


def layer_matmul_params(sizes):
    """Weights of one block's matmuls: the mixer's in_proj and out_proj,
    q, k, v, o, and the three of the SwiGLU."""
    D, F = sizes["hidden_size"], sizes["intermediate_size"]
    d_ssm = sizes["mamba_d_ssm"]
    GN = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    q = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    return D * (2 * d_ssm + 2 * GN + sizes["mamba_n_heads"]) + d_ssm * D \
        + D * (q + 2 * kv) + q * D + 3 * D * F


def head_matmul_params(sizes):
    """The untied output projection."""
    return sizes["hidden_size"] * sizes["vocab_size"]


def attention_flops(sizes, context):
    """One query row against ``context`` keys, all query heads, one
    layer: q.k and p.v, each 2 * context * heads * head_dim."""
    return 4 * context * sizes["num_attention_heads"] * sizes["head_dim"]


def scan_flops(sizes):
    """One row of the recurrence, one layer: decay, update and read-out
    of the (d_ssm, state) state, 6 operations an element."""
    return 6 * sizes["mamba_d_ssm"] * sizes["mamba_d_state"]


def serve_flops(sizes, rows, context_sum, sampled):
    """Forward of ``rows`` token rows whose causal contexts add up to
    ``context_sum`` keys, ``sampled`` of them followed by the head."""
    L = sizes["num_hidden_layers"]
    return float(L * ((2 * layer_matmul_params(sizes) + scan_flops(sizes))
                      * rows
                      + attention_flops(sizes, 1) * context_sum)
                 + 2 * head_matmul_params(sizes) * sampled)


def slot_state_bytes(sizes, state_bytes=4, window_bytes=2):
    """One slot's state of one layer: the SSM state (heads x head x
    state, float32) and the convolution window (taps - 1 inputs of
    x | B | C, bfloat16)."""
    conv_dim = sizes["mamba_d_ssm"] \
        + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return sizes["mamba_d_ssm"] * sizes["mamba_d_state"] * state_bytes \
        + (sizes["mamba_d_conv"] - 1) * conv_dim * window_bytes


def ssm_state_bytes(sizes, updates, **kw):
    """Bytes ``updates`` slot-state updates move: each reads and writes
    one state in every layer."""
    return 2 * updates * sizes["num_hidden_layers"] \
        * slot_state_bytes(sizes, **kw)
