"""kernel_costs.py against counts worked by hand for one Mistral-7B layer."""
import json
from pathlib import Path

import pytest

from benchmark import kernel_costs

CONFIG = json.loads(
    (Path(__file__).parents[1] / "configs" / "mistral-7b-v0.3-l16.json").read_text())


def test_matmul_params_one_layer_and_head():
    one = dict(CONFIG, num_hidden_layers=1)
    # wq 4096x4096, wk and wv 4096x1024, wo 4096x4096, three 4096x14336, head 4096x32768
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert kernel_costs.matmul_params(one) == layer + 4096 * 32768
    assert kernel_costs.matmul_params(CONFIG) == 16 * layer + 134_217_728


def test_train_flops_per_token():
    one = dict(CONFIG, num_hidden_layers=1)
    mm = 6 * (218_103_808 + 134_217_728)
    # 2048.5 keys on average; 4*H*hd FLOPs a key forward, three times that in all
    attn = 3 * 4 * 32 * 128 * 2048.5
    assert kernel_costs.train_flops_per_token(one, 4096) == pytest.approx(mm + attn)


def test_flash_calls():
    fwd = kernel_costs.flash_forward(2, 4096, 32, 8, 128)
    pairs = 2 * 32 * 4096 * 4097 / 2
    assert fwd["flops"] == pytest.approx(4 * pairs * 128)
    assert fwd["bytes"] == 2 * (2 * 4096 * 32 * 128 * 2) + 2 * (2 * 4096 * 8 * 128 * 2) + 2 * 32 * 4096 * 4
    bwd = kernel_costs.flash_backward(2, 4096, 32, 8, 128)
    assert bwd["flops"] == pytest.approx(2 * fwd["flops"])
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = kernel_costs.least_seconds(fwd, peak)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(fwd["flops"] / 197e12)
