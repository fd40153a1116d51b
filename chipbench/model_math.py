"""Operations a model needs, from its shapes alone: what the algorithm
requires, whatever implements it.  Recomputation is not counted, and the
output head is counted only where the algorithm needs its result (the
masked positions in MLM pretraining, the sampling rows in serving).  A
multiply-add is two operations."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The chip's published peaks; a device that is not in the table is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r in peaks.json"
                       % (device_kind,))
    return table[device_kind]


def layer_matmul_params(sizes):
    """Weights of one block's matmuls: q, k, v, o and the two of the FFN."""
    D, F = sizes["hidden_size"], sizes["intermediate_size"]
    return 4 * D * D + 2 * D * F


def head_matmul_params(sizes):
    """The head's transform and the tied output projection."""
    D, V = sizes["hidden_size"], sizes["vocab_size"]
    return D * D + D * V


def attention_flops(sizes, context):
    """One query row against ``context`` keys, all heads, one layer:
    q.k and p.v, each 2 * context * D."""
    return 4 * context * sizes["hidden_size"]


def train_flops_per_token(sizes, seq_len, head_share):
    """Forward plus backward (3 x forward) of one token of a sequence of
    ``seq_len`` under bidirectional attention; ``head_share`` is the share
    of positions whose logits the loss needs."""
    L = sizes["num_hidden_layers"]
    fwd = L * (2 * layer_matmul_params(sizes)
               + attention_flops(sizes, seq_len)) \
        + head_share * 2 * head_matmul_params(sizes)
    return 3.0 * fwd


def serve_flops(sizes, rows, context_sum, sampled):
    """Forward of ``rows`` token rows whose causal contexts add up to
    ``context_sum`` keys, ``sampled`` of them followed by the head."""
    L = sizes["num_hidden_layers"]
    return float(L * (2 * layer_matmul_params(sizes) * rows
                      + attention_flops(sizes, 1) * context_sum)
                 + 2 * head_matmul_params(sizes) * sampled)
