"""The least time a chip could take for the traced blocks' token samples
(``perf/work_models/lda_token_sample.py``) over the device time inside
the LDA Mosaic call, the only Mosaic call of the sweep program: the
reading MF-SGD's kernel has, of another job's only kernel."""

from perf.layer_metrics.mfsgd_kernel_roofline import read  # noqa: F401
