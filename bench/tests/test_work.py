"""Work counts against hand counts at two shapes each."""
import pytest

import work

EMNIST = {"kind": "mlp", "in_dim": 784, "hidden": [48], "n_classes": 62}
EMNIST200 = {"kind": "mlp", "in_dim": 784, "hidden": [200], "n_classes": 62}
CIFAR = {"kind": "mlp", "in_dim": 3072, "hidden": [48], "n_classes": 100}


@pytest.mark.parametrize("model, flops, params", [
    (EMNIST, 6 * (784 * 48 + 48 * 62), 784 * 48 + 48 + 48 * 62 + 62),
    (EMNIST200, 6 * (784 * 200 + 200 * 62), 784 * 200 + 200 + 200 * 62 + 62),
    (CIFAR, 6 * (3072 * 48 + 48 * 100), 3072 * 48 + 48 + 48 * 100 + 100),
])
def test_train_flops_and_params(model, flops, params):
    assert work.train_flops_per_example(model) == flops
    assert work.param_count(model) == params


def test_hand_counts_match_the_issue():
    assert work.train_flops_per_example(EMNIST) == 243_648
    assert work.train_flops_per_example(CIFAR) == 913_536
    assert work.param_count(EMNIST) == 40_718
    assert work.param_count(CIFAR) == 152_404
    assert work.train_flops_per_example(EMNIST200) == 1_015_200
    assert work.param_count(EMNIST200) == 169_462


@pytest.mark.parametrize("m, n, t, quant, nbytes, flops", [
    # rows read once, base read and result written, 4 bytes a value
    (512, 40_718, 16, False, 4 * (512 * 40_718 + 2 * 16 * 40_718),
     2 * 512 * 40_718),
    (8, 100, 2, True, 4 * (8 * 100 + 3 * 2 * 100), 8 * 8 * 100),
])
def test_fed_reduce_traffic(m, n, t, quant, nbytes, flops):
    assert work.fed_reduce_traffic(m, n, t, quant=quant) == (nbytes, flops)


def test_roofline_picks_the_larger_term():
    peak = {"hbm_bytes_per_s": 819e9, "peak_flops_bf16": 197e12}
    nbytes, flops = work.fed_reduce_traffic(512, 40_718, 16)
    t, bound = work.roofline_seconds(nbytes, flops, peak)
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)
    t, bound = work.roofline_seconds(1.0, 1e12, peak)
    assert bound == "flops" and t == pytest.approx(1e12 / 197e12)
