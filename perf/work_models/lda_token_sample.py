"""One token's topic resampled once, whatever implements it: the token's
K-wide float32 doc-topic row and word-topic row read (8 x K bytes; N_k is
one row for every token and stays on the chip) and four counts written
(16 bytes: the old and the new topic's entry in each table); the K
posterior terms ``(N_dk + alpha)(N_wk + beta) / (N_k + V beta)`` and the
draw among them (three adds, a multiply, a divide and a compare a topic:
6 x K FLOPs at the f32 rate, and K random numbers).  Tiles, padding,
one-hot matmuls and the kernel's count gathers are one implementation's
and are never counted.  HBM binds: 9.8 ns a token at K = 1000, against
0.12 ns of arithmetic."""


def per_item(work: dict) -> dict:
    k = work["n_topics"]
    return {"flops": 6.0 * k, "bytes": 8.0 * k + 16.0, "peak": "f32_flops"}
