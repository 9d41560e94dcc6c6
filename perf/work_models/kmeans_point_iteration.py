"""One point through one Lloyd iteration: the distance matmul (2dk) and
the one-hot sums matmul (2kd); the point's row read once and its int32
assignment written once.  f32 dots run as single bf16 MXU passes at JAX's
default precision, so the compute wall is the bf16 peak (``roofline.py``:
131 TFLOP/s measured against a 49 TFLOP/s f32 peak proved it).
Arithmetic copied from ``harp_tpu/utils/roofline.py`` ``_kmeans_work``."""


def per_item(work: dict) -> dict:
    d, k = work["d"], work["k"]
    return {"flops": 4.0 * d * k, "bytes": work["point_bytes"] * d + 4.0,
            "peak": "bf16_flops"}
