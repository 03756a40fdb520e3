"""Operations and bytes one ``flash_attention_window`` call needs.

A call attends B sequences of S queries (H heads) over the same S keys
(HK kv heads) of width Dh, each query seeing the ``window`` keys up to
and including its own position: W S - W (W - 1) / 2 (query, key) pairs
when W < S, the causal triangle otherwise. Operations: one multiply-add
per (pair, head, dim) for the scores and one for the weighted values.
Bytes: q, k and v read once and the output written once.
"""


def cost(call: dict):
    B, H, HK, Dh = call["B"], call["H"], call["HK"], call["Dh"]
    S, W = call["Sq"], call["window"]
    pairs = W * S - W * (W - 1) // 2 if W < S else S * (S + 1) // 2
    ops = 4 * B * H * Dh * pairs
    nbytes = call.get("itemsize", 4) * B * Dh * (2 * H * S + 2 * HK * S)
    return ops, nbytes, "bf16_flops"
