"""Operations and bytes one ``grouped_matmul`` call needs.

A call multiplies the rows routed to each held expert by that expert's
(K, N) matrix: ``rows`` lists the rows of each expert. Operations: 2 K N
per row. Bytes: the matrices of the experts that got a row, read once,
and every row read in (K) and written out (N) once, at ``itemsize``
bytes a value (float32 unless stated). Counting what the algorithm needs,
and not the padding or the worst-case buffer the kernel walks, keeps the
share at or under 100%.
"""


def cost(call: dict):
    rows, K, N = call["rows"], call["K"], call["N"]
    item = call.get("itemsize", 4)
    total = sum(rows)
    ops = 2 * total * K * N
    nbytes = item * (sum(1 for r in rows if r) * K * N + total * (K + N))
    return ops, nbytes, "bf16_flops"
