"""One visited rating: dot(W_u, H_i) and two axpy rows (6 x rank FLOPs);
both f32 rows read and written (16 x rank bytes).  Arithmetic copied from
``harp_tpu/utils/roofline.py`` ``_mfsgd_work``."""


def per_item(work: dict) -> dict:
    r = work["rank"]
    return {"flops": 6.0 * r, "bytes": 16.0 * r, "peak": "bf16_flops"}
