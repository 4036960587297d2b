"""FLOP and byte counts against hand-worked numbers."""
import pytest

from bench import work
from bench.weights import dims_of, load_config, param_count
from bench.tests.conftest import REPO

Q05 = dims_of(load_config(f"{REPO}/bench/configs/qwen1.5-0.5b.json"))
Q14 = dims_of(load_config(f"{REPO}/bench/configs/qwen2.5-14b-pp4.json"))


def test_param_counts():
    assert param_count(Q05) == 463_987_712          # tied head
    assert param_count(Q14) == 4_860_363_776        # 12 layers, untied


def test_forward_flops_qwen05_at_1024():
    # matmul weights: 24 x (2*1024*1024 + 2*1024*1024 + 3*1024*2816)
    # + head 1024*151936 = 308,281,344 + 155,582,464
    mm = 308_281_344 + 155_582_464
    assert work.matmul_params(Q05) == mm
    # one causal sequence of 1024 tokens through the whole model: 2 per
    # weight and token, plus 1024*1025/2 query-key pairs, 4 FLOPs per pair
    # and q-width unit, 24 layers
    attn = 4 * 24 * 1024 * 1024 * 1025 / 2
    assert work.forward_flops(Q05, 1024, 1024 * 1025 / 2) == pytest.approx(
        2 * mm * 1024 + attn)
    assert work.forward_flops(Q05, 1024, 1024 * 1025 / 2) == pytest.approx(
        1.001583017984e12)


def test_prefill_flops_counts_head_once():
    s = 512
    body = 2 * (work.matmul_params(Q14) - Q14.d * Q14.vocab) * s
    attn = 4 * Q14.layers * Q14.nq * s * (s + 1) / 2
    head = 2 * Q14.d * Q14.vocab
    assert work.prefill_flops(Q14, s) == pytest.approx(body + attn + head)


FLASH = ('%closed_call.38 = (bf16[64,1024,64]{2,1,0:T(8,128)(2,1)}, '
         'f32[64,1024,1]{2,1,0:T(8,128)}) custom-call(bf16[64,1024,64]'
         '{2,1,0:T(8,128)(2,1)} %a, bf16[64,1024,64]{2,1,0:T(8,128)(2,1)} '
         '%b, bf16[64,1024,64]{2,1,0:T(8,128)(2,1)} %c), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints='
         '{bf16[64,1024,64]{2,1,0}}')


def test_flash_causal_work_at_1024():
    assert work.kernel_of(FLASH) == "flash_fwd"
    flops, nbytes = work.flash_fwd_work(FLASH)
    # 64 head-rows of 64 wide: 4 * 64 * 64 * 1024*1025/2 causal pairs
    assert flops == 4 * 64 * 64 * 1024 * 1025 / 2
    # q, k, v and the output in bf16, the row log-sum-exp in f32, once
    assert nbytes == 4 * 64 * 1024 * 64 * 2 + 64 * 1024 * 4
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.least_seconds(flops, nbytes, peak) == pytest.approx(
        flops / 197e12)


def test_vmem_operands_do_not_count_against_hbm():
    onchip = FLASH.replace("(2,1)}", "(2,1)S(1)}")
    _, nbytes = work.flash_fwd_work(onchip)
    assert nbytes == 64 * 1024 * 4          # only the f32 lse is in HBM


def test_codec_kernels_classified():
    q = ('%c.1 = (s8[256,512]{1,0}, f32[1,512]{1,0}) custom-call('
         'f32[256,512]{1,0} %x), custom_call_target="tpu_custom_call"')
    d = ('%c.2 = f32[256,512]{1,0} custom-call(s8[256,512]{1,0} %q, '
         'f32[1,512]{1,0} %s), custom_call_target="tpu_custom_call"')
    assert work.kernel_of(q) == "int8_quant"
    assert work.kernel_of(d) == "int8_dequant"
    assert work.io_work(q)[1] == 256 * 512 * 4 + 256 * 512 + 512 * 4
    assert work.kernel_of('%f = f32[2]{0} fusion(f32[2]{0} %x)') is None
