"""One sample through one synchronous SGD step of a dense MLP, whatever
implements it: the forward matmuls (2 x fan_in x fan_out a layer), the
weight gradients (the same again) and the input gradients of every layer
but the first (the table's rows need none); the sample's float32 row and
its int32 label read once (4 x d + 4 bytes).  Padded widths, the softmax,
the bias gradients and the parameter update (whose bytes are shared by
the whole batch) are one implementation's and are never counted.  f32
dots run as single bf16 MXU passes at JAX's default precision, so the
compute wall is the bf16 peak, as for ``kmeans_point_iteration``."""


def per_item(work: dict) -> dict:
    sizes = work["sizes"]
    layers = [fi * fo for fi, fo in zip(sizes[:-1], sizes[1:])]
    return {"flops": 2.0 * (2 * sum(layers) + sum(layers[1:])),
            "bytes": 4.0 * sizes[0] + 4.0, "peak": "bf16_flops"}
