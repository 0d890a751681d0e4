"""The FLOP and byte functions against numbers known from elsewhere."""

from cell import cell_spec, flops_per_step, load_module, peaks


def test_resnet50_forward_is_4_1_gmac():
    macs = load_module("flops", "resnet_v1_50").forward_macs(224, 1000)
    assert abs(macs / 4.1e9 - 1.0) < 0.01


def test_cnnet_forward_by_hand():
    # conv1 1024x75x64, conv2 256x1600x64, dense 4096x384, 384x192, 192x10
    assert load_module("flops", "cnnet").forward_macs(32, 10) == (
        4915200 + 26214400 + 1572864 + 73728 + 1920)


def test_flops_per_step_counts_every_image_forward_and_backward():
    spec = cell_spec("resnet50_bulyan_1chip")
    images = spec["config_data"]["nb_workers"] * spec["config_data"]["batch_per_worker"]
    assert flops_per_step(spec) == 6 * images * load_module(
        "flops", "resnet_v1_50").forward_macs(224, 1000)


def test_rule_bytes():
    n, f, d = 32, 7, 1000
    assert load_module("rules", "average").least_bytes(n, f, d) == 33 * d * 4
    assert load_module("rules", "bulyan").least_bytes(n, f, d) == (32 + 23 + 1) * d * 4
    assert load_module("rules", "krum").least_bytes(8, 2, d) == (8 + 4 + 1) * d * 4


def test_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 1.97e14
    try:
        peaks("TPU v9 imaginary")
    except SystemExit as error:
        assert "TPU v9 imaginary" in str(error)
    else:
        raise AssertionError("an unknown device kind must raise")
