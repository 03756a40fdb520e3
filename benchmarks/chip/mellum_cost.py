"""Model operations of the Mellum configuration served here, from its
sizes and each request's routing.

Counted as the algorithm needs them: two operations per multiply-add of
the four attention projections and of the router over all E experts at
every token; of the held experts' three matrices at the rows routed to
them (the request's own routing, not an average); attention over the
causal pairs of the full layers and the window's band of the sliding
ones; and the output head only at the positions whose logits are used.
Norms, rotations and activations are left out (a few per cent).
"""


def pairs(S: int, window=None) -> int:
    """(query, key) pairs of causal self-attention over S tokens, or of a
    sliding window W: W S - W (W - 1) / 2."""
    if window is None or window >= S:
        return S * (S + 1) // 2
    return window * S - window * (window - 1) // 2


def prefill(dm: dict, S: int, expert_rows: int, head_rows: int = 1) -> float:
    """One sequence of S tokens whose held experts were routed
    ``expert_rows`` rows over all layers; logits at ``head_rows``
    positions."""
    d, H, HK, Dh, F = dm["d"], dm["H"], dm["HK"], dm["Dh"], dm["F"]
    per_token = 2 * d * H * Dh + 2 * d * HK * Dh + d * dm["E"]
    attn = sum(4 * H * Dh * pairs(S, None if full else dm["W"])
               for full in dm["full"])
    return 2.0 * dm["L"] * per_token * S + 6.0 * d * F * expert_rows \
        + attn + 2.0 * d * dm["V"] * head_rows
