"""Share of its roofline that the int8 wire codec (quantize and dequantize
kernels) reaches: bytes each call reads and writes over HBM at the HBM
bandwidth, over the calls' device time."""
from bench import readers


def read(r):
    return readers.roofline_pct(r, ("int8_quant", "int8_dequant"))
