"""Work counts: operations and bytes that an algorithm needs, from shapes.

Model FLOPs count the matrix products of the forward pass, the head included and the embedding lookup left out,
plus attention's scores and values over the causal half only.  Kernel work
is read from each call's operand and result shapes in its HLO text, so it is
the work that call has to do, whatever implements it: flash attention over
the causal half of its keys, the int8 codec reading and writing each byte
once.  Arrays that the compiler has already placed in on-chip memory do not
cross HBM and are not counted against its bandwidth.  A kernel that skips
dead blocks or fuses a pass does less than this and its share of the
roofline rises; none can pass 100%.
"""
from __future__ import annotations

import re

from bench.weights import Dims

_DT = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4, "u32": 4,
       "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE = re.compile(r"\b(bf16|f16|f32|s8|u8|s32|u32|pred|f8e4m3fn|f8e5m2)"
                    r"\[([0-9,]*)\](\{[^}]*\})?")


def shapes(text: str) -> list:
    """[(dtype, dims, in_hbm)] of every array shape written in `text`, in
    order.  A layout that names memory space 1 (`S(1)`) is the on-chip
    vector memory, where the compiler has already placed the array; any
    other array lives in HBM."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x),
             "S(1)" not in layout)
            for dt, dims, layout in _SHAPE.findall(text)]


def nbytes(shape) -> int:
    dt, dims = shape[0], shape[1]
    n = 1
    for x in dims:
        n *= x
    return n * _DT[dt]


def hbm_bytes(shapes_) -> float:
    """Bytes of the arrays that have to cross HBM."""
    return float(sum(nbytes(s) for s in shapes_ if s[2]))


def split_call(text: str):
    """(result shapes, operand shapes) of one HLO instruction's text."""
    head, _, rest = text.partition(" = ")
    m = re.match(r"(\([^=]*?\)|\S+)\s+[\w-]+\((.*?)\)(,|$)", rest)
    if not m:
        return [], []
    return shapes(m.group(1)), shapes(m.group(2))


def kernel_of(text: str):
    """Which Pallas kernel a `tpu_custom_call` is, from its signature:
    'flash_fwd', 'rmsnorm', 'int8_quant', 'int8_dequant', or None."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    res, ops = split_call(text)
    if len(ops) == 3 and all(len(s[1]) == 3 for s in ops) \
            and ops[0][0] in ("bf16", "f32", "f16"):
        return "flash_fwd"
    if len(ops) == 2 and len(ops[0][1]) == 2 and len(ops[1][1]) == 1:
        return "rmsnorm"
    if len(ops) == 1 and len(res) == 2 and res[0][0] == "s8":
        return "int8_quant"
    if len(ops) == 2 and ops[0][0] == "s8":
        return "int8_dequant"
    return None


def flash_fwd_work(text: str) -> tuple:
    """(FLOPs, bytes) that one causal flash-attention forward call needs:
    q (BH, S, D) against k, v (BKH, S, D); scores and values over the
    causal half; q, k, v read and the output (and row log-sum-exp) written
    once."""
    res, ops = split_call(text)
    (bh, sq, d), (_, sk, _) = ops[0][1], ops[1][1]
    pairs = sq * (sq + 1) / 2 if sq == sk else sq * sk
    return 4.0 * bh * d * pairs, hbm_bytes(ops + res)


def io_work(text: str) -> tuple:
    """(FLOPs, bytes) of an elementwise kernel: operands read and results
    written once; one operation per element of the first operand."""
    res, ops = split_call(text)
    n = 1
    for x in ops[0][1]:
        n *= x
    return float(n), hbm_bytes(ops + res)


KERNEL_WORK = {"flash_fwd": flash_fwd_work, "rmsnorm": io_work,
               "int8_quant": io_work, "int8_dequant": io_work}


def least_seconds(flops: float, nbytes_: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["flops"], nbytes_ / peak["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def matmul_params(dm: Dims) -> int:
    """Weights that take part in a matrix product per token: the layers'
    projections and MLP, and the head (the embedding lookup is not one)."""
    per_layer = 2 * dm.d * dm.nq + 2 * dm.d * dm.nkv + 3 * dm.d * dm.ff
    return dm.layers * per_layer + dm.d * dm.vocab


def forward_flops(dm: Dims, tokens: int, keys_sum: float) -> float:
    """Forward FLOPs of `tokens` tokens whose attention spans `keys_sum`
    keys in all (each token attends to itself and the keys before it)."""
    return (2.0 * matmul_params(dm) * tokens
            + 4.0 * dm.layers * dm.nq * keys_sum)


def prefill_flops(dm: Dims, s: int) -> float:
    """A prompt of s tokens, causal: s(s+1)/2 query-key pairs.  The head is
    applied to the last position only, as prefill does."""
    body = (forward_flops(dm, s, s * (s + 1) / 2)
            - 2.0 * dm.d * dm.vocab * s)
    return body + 2.0 * dm.d * dm.vocab
