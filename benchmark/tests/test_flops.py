"""The FLOP functions against hand counts."""
import json
import os

from benchmark.harness import cells, flops


def _count(config):
    path = os.path.join(cells.BENCH_DIR, "configs", config + ".json")
    with open(path) as f:
        cfg = json.load(f)
    adapter = cells.load_module(path[:-5] + "_program.py")
    return adapter.flops_per_sample(cfg, flops)


def test_resnet50_flops_per_image():
    c = _count("resnet50_v1")
    # By hand: stem 7x7x3x64 at 112^2 = 118.0 MMAC; stages 1..4 (stride on
    # the first 1x1, as the model zoo has it) 667.9 + 950.5 + 1387.3 +
    # 732.2 MMAC; head 2.05 MMAC; total 3.858 GMAC forward.
    assert abs(c.forward_macs - 3.858e9) / 3.858e9 < 1e-3
    # forward + backward = 3x, the stem's input gradient not needed:
    # 6 * 3.858e9 - 2 * 118.0e6 = 22.91 GFLOP; PR 22's 3.64% MFU at
    # 1,251 img/s on four chips implies 22.9 (ledger, PR 22).
    assert abs(c.train_flops - 22.91e9) / 22.91e9 < 1e-3
    assert abs(c.param_bytes_f32 / 4 - 25.5e6) / 25.5e6 < 2e-2


def test_lstm_flops_per_token():
    c = _count("lstm_ptb")
    # 2 layers x 8 * 1500^2 MACs + 1500 * 10^4 MACs = 51 MMAC; x2 x3.
    assert c.forward_macs == 2 * 8 * 1500 ** 2 + 1500 * 10 ** 4
    assert c.train_flops == 306_000_000


def test_conv_and_dense_counts():
    c = flops.Count()
    c.conv(3, 8, 3, 10, 10, first=True)      # 3*8*9*100 = 21,600 MAC
    c.dense(16, 4)                            # 64 MAC
    assert c.forward_macs == 21_600 + 64
    assert c.train_flops == 2 * 21_600 * 2 + 2 * 64 * 3
