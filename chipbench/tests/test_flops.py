import pytest

from chipbench import common, flops
from conftest import ROOT

QWEN = common.load_json(ROOT / "chipbench/configs/qwen1.5-4b.json")
CNN = common.load_json(ROOT / "chipbench/configs/flight-cnn-cifar.json")


def test_qwen_parameter_count_by_hand():
    # q, k, v: 2560 x 20 x 128 each; o: 2560 x 2560; MLP: 3 x 2560 x 6912
    per_layer = 3 * 2560 * 2560 + 2560 * 2560 + 3 * 2560 * 6912
    assert flops.decoder_layer_params(QWEN) == per_layer == 79_298_560
    head = 2560 * 151936
    assert 40 * per_layer + head == 3_560_898_560


def test_qwen_decode_and_prefill_by_hand():
    n = 2 * (40 * 79_298_560 + 2560 * 151936)
    assert flops.decoder_decode(QWEN, 1000) == n + 4 * 40 * 2560 * 1000
    T = 64
    want = (2 * T * 40 * 79_298_560 + 4 * 40 * 2560 * T * (T + 1) / 2
            + 2 * 2560 * 151936)
    assert flops.decoder_prefill(QWEN, T) == want


def test_cnn_forward_and_params_by_hand():
    conv0 = 2 * 32 * 32 * 9 * 3 * 32
    conv1 = 2 * 16 * 16 * 9 * 32 * 64
    fc = 2 * 8 * 8 * 64 * 10
    assert flops.cnn_forward(CNN) == conv0 + conv1 + fc == 11_288_576
    shapes = flops.cnn_params(CNN)
    assert sum(__import__("math").prod(s) for s in shapes.values()) == \
        CNN["parameters"] == 60362


def test_quant8_bytes_by_hand():
    assert flops.quant8_bytes((256, 4096, 10)) == \
        5 * 256 * 4096 * 10 + 4 * 256 * 4096
    assert flops.quant8_bytes((7, 32)) == 5 * 224 + 4 * 7


def test_peaks_refuse_an_unknown_chip():
    from chipbench import peaks
    assert peaks.of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.of("cpu")
