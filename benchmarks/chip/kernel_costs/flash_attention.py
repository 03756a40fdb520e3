"""Operations and bytes one ``flash_attention`` call needs.

A call attends B sequences of Sq queries (H heads) over Skv keys (HK kv
heads) of width Dh. Causal attention needs only the keys at or before
each query: Sq * (Sq + 1) / 2 score rows for aligned self-attention.
Operations: one multiply-add per (query, key, head, dim) for the scores
and one for the weighted values. Bytes: q, k and v read once and the
output written once. Counting what the algorithm needs, and not what a
kernel happens to do, keeps the share at or under 100%.
"""


def cost(call: dict):
    B, H, HK, Dh = call["B"], call["H"], call["HK"], call["Dh"]
    Sq, Skv = call["Sq"], call["Skv"]
    pairs = Sq * (Sq + 1) // 2 if call.get("causal", True) else Sq * Skv
    ops = 4 * B * H * Dh * pairs
    item = call.get("itemsize", 4)
    nbytes = item * B * Dh * (2 * H * Sq + 2 * HK * Skv)
    return ops, nbytes, "bf16_flops"
