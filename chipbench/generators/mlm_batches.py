"""Generator ``mlm_batches``: masked-LM pretraining batches on the host.

``distinct_batches`` batches of ``batch`` x ``seq_len``: token ids uniform
over the vocabulary from the seed, exactly ``round(mask_share * seq_len)``
positions of every row replaced by ``mask_token_id`` with the original id
as the label (-100 elsewhere), the first half of a row segment 0 and the
second segment 1.  Every seed gives the same amount of work: the same
shapes and the same count of masked positions."""
import numpy as np


def masked_per_row(traffic):
    return int(round(traffic["mask_share"] * traffic["seq_len"]))


def generate(traffic, sizes, seed):
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    B, T = traffic["batch"], traffic["seq_len"]
    n_mask = masked_per_row(traffic)
    type_ids = np.zeros((B, T), np.int32)
    if sizes["type_vocab_size"] > 1:
        type_ids[:, T // 2:] = 1
    batches = []
    for _ in range(traffic["distinct_batches"]):
        ids = rng.randint(1, sizes["vocab_size"], (B, T)).astype(np.int32)
        labels = np.full((B, T), -100, np.int32)
        tokens = ids.copy()
        for row in range(B):
            at = rng.permutation(T)[:n_mask]
            labels[row, at] = ids[row, at]
            tokens[row, at] = traffic["mask_token_id"]
        batches.append({"tokens": tokens, "labels": labels,
                        "type_ids": type_ids.copy()})
    return batches
