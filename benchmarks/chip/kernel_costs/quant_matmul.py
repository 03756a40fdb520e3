"""Operations and bytes one ``quant_matmul`` call needs.

int8 codes x (M, K) times int8 codes w (K, N), with one float32 scale per
row of x and per column of w, into a float32 (M, N) output. Operations:
2 M K N on the chip's int8 path. Bytes: both code matrices and both
scale vectors read once, the output written once.
"""


def cost(call: dict):
    M, K, N = call["M"], call["K"], call["N"]
    ops = 2 * M * K * N
    nbytes = M * K + K * N + 4 * (M + N) + 4 * M * N
    return ops, nbytes, "int8_ops"
