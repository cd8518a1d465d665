"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  A device
whose kind is not here is refused, never given a default.  The peaks are one
chip's: a reader of a cell on several chips multiplies them by the cell's
``chips`` (``Window.chips``).
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}
