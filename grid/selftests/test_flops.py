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


def test_step_mfu_is_the_model_s_flops_over_the_device_s_step_time():
    """On four chips at the ledger's PR 41 reading: 68.575 ms a step, where
    ``mfu_pct`` reads 5.811 at 14.581 steps/s (idle share 2.6-3.1 %)."""
    spec = cell_spec("resnet50_bulyan_4chip")
    ctx = {"cell": spec, "peaks": peaks("TPU v5 lite"), "trace": {"device_step_ms": 68.575}}
    value = load_module("layer_metrics", "step_mfu_pct").read(ctx)
    assert value == 100 * flops_per_step(spec) / 68.575e-3 / (4 * 1.97e14)
    assert 5.811 < value < 5.811 * 1.001  # 1000 / 68.575 = 14.5826 steps/s for 14.581


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
