"""Model operations of the dense decoder served here, from its sizes.

Counted as the algorithm needs them: two operations per multiply-add of
every linear projection, causal attention over the positions each query
sees, and the output head only at the positions whose logits are used.
Norms, rotations and activations are left out (a few per cent).
"""


def linear_params(dm: dict) -> int:
    d, H, HK, Dh, F = dm["d"], dm["H"], dm["HK"], dm["Dh"], dm["F"]
    return dm["L"] * (2 * d * H * Dh + 2 * d * HK * Dh + 3 * d * F)


def prefill(dm: dict, S: int, head_rows: int = 1) -> float:
    """One sequence of S tokens, logits at ``head_rows`` positions."""
    attn = 4 * dm["L"] * dm["H"] * dm["Dh"] * (S * (S + 1) // 2)
    return 2.0 * linear_params(dm) * S + attn \
        + 2.0 * dm["d"] * dm["V"] * head_rows

