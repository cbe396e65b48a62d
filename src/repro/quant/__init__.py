from .lut import build_lut, exact_mul_lut
from .int4 import (PreparedWeight, approx_linear, dequantize, prepare_weight,
                   quantize_int4, quantize_intb)

__all__ = [
    "build_lut",
    "exact_mul_lut",
    "quantize_int4",
    "quantize_intb",
    "approx_linear",
    "prepare_weight",
    "PreparedWeight",
    "dequantize",
]
