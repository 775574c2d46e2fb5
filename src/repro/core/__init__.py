# The paper's primary contribution: the butterfly unit (reduction/restoration
# bottleneck + int8 wire), Algorithm 1 (train/profile/select partitioning),
# and the wireless/roofline profiling substrate.
from repro.core.butterfly import (
    apply_butterfly,
    butterfly_wire_bytes,
    compression_ratio,
    init_butterfly,
)
from repro.core.quantization import (Wire, dequantize, fake_quant, quantize,
                                     wire_bytes)

__all__ = [
    "apply_butterfly", "butterfly_wire_bytes", "compression_ratio",
    "init_butterfly", "Wire", "dequantize", "fake_quant", "quantize",
    "wire_bytes",
]
