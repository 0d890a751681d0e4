"""The Laguna cell's own pieces: the FLOP function against a count by hand and
the allowed pairs of each layer kind against a counted mask, the parameters
against the configuration's count, the configuration's lists against its
shape, and a whole run off the chip at a tiny size — ``correct`` true for the
sound path, false with the timed path given the wrong mask underneath (its
sliding layers run a window of half the width, or none)."""

import json

import numpy as np
import pytest

from cell import cell_spec, flops_per_step, load_module

WORKLOAD = "laguna_avgmedian_causal4k"


@pytest.mark.parametrize("length,window", [(4096, None), (4096, 512), (40, 12), (8, 12), (40, 1)])
def test_allowed_pairs_against_a_counted_mask(length, window):
    flops, reference = load_module("flops", "laguna"), load_module("references", "laguna")
    assert flops.allowed_pairs(length, window) == int(np.sum(np.asarray(
        reference.causal_mask(length, window))))


def test_forward_macs_by_hand():
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    flops = load_module("flops", "laguna")
    assert flops.allowed_pairs(4096) == 8390656 and flops.allowed_pairs(4096, 512) == 1966336
    attention = lambda heads: 2 * 2048 * heads * 128 + 2 * 2048 * 4 * 128   # q and o, k and v: half the heads held
    sparse = 2048 * 256 + 3 * 2048 * 512 + 8 * 8 / 256 * 3 * 2048 * 512     # router, shared, a quarter visit
    layers = (4096 * (attention(24) + 3 * 2048 * 8192) + 2 * 8390656 * 24 * 128      # layer 0
              + 3 * (4096 * (attention(32) + sparse) + 2 * 1966336 * 32 * 128)        # layers 1-3
              + 4096 * (attention(24) + sparse) + 2 * 8390656 * 24 * 128)             # layer 4
    by_hand = int(layers + 4096 * 2048 * 12544)
    assert by_hand == 888015945728
    assert flops.forward_macs(config["image_size"], config["classes"]) == by_hand
    assert flops_per_step(spec) == 6 * by_hand * 3  # 15.98 TFLOP a step
    whole = dict(config["image_size"], num_key_value_heads=8,
                 num_attention_heads_per_layer=[48, 64, 64, 64, 48])
    assert flops.forward_macs(whole, config["classes"]) == 1391632318464  # every head held
    pairs = 2 * (2 * 8390656 * 24 * 128) + 3 * (2 * 1966336 * 32 * 128)
    assert round(pairs / 1e9, 1) == 151.4  # of which 68 % in the two full layers
    assert round(100 * 2 * (2 * 8390656 * 24 * 128) / pairs) == 68


def test_parameters_are_the_raveled_state():
    import jax

    config = cell_spec(WORKLOAD)["config_data"]
    shapes = jax.eval_shape(lambda key: load_module("references", "laguna").init(
        key, config["image_size"], config["classes"]), jax.random.PRNGKey(0))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) \
        == config["parameters"] == 303060992


def test_the_shape_is_the_head_of_the_lists_and_half_the_heads():
    config = cell_spec(WORKLOAD)["config_data"]
    shape, depth = config["image_size"], config["num_hidden_layers"]
    assert len(config["layer_types"]) == 40 and depth == 5
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert shape[key] == config[key][:depth], key
    for key in ("hidden_size", "head_dim", "num_key_value_heads", "sliding_window",
                "intermediate_size", "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "moe_routed_scaling_factor",
                "rope_parameters", "rms_norm_eps"):
        assert shape[key] == config[key], key
    assert shape["num_attention_heads_per_layer"] == [24, 32, 32, 32, 24]  # of 48 and 64
    assert shape["num_key_value_heads"] == 4 and config["published"]["num_key_value_heads"] == 8
    assert shape["num_experts"] == 256 and len(shape["experts_held"]) == config["num_experts"] == 8
    assert config["classes"] == config["vocab_size"] == 100352 // 8
    assert "heads:24,32,32,32,24" in config["experiment_args"]


def test_plain_rule_and_its_bytes():
    import jax.numpy as jnp

    from aggregathor_tpu import gars

    rows = np.random.default_rng(0).normal(size=(3, 1000)).astype(np.float32)
    rows[1, 100:300] = 0.0          # a worker no token of which reached an expert
    rows[2, 200:400] = 0.0
    rows[2, 5], rows[0, 6] = np.nan, np.inf
    rule = load_module("rules", "averaged-median")
    ours = np.asarray(gars.instantiate("averaged-median", 3, 1).aggregate(jnp.asarray(rows)))
    assert np.array_equal(ours, np.asarray(rule.aggregate(jnp.asarray(rows), 1)))
    assert rule.least_bytes(3, 1, 10) == 4 * 10 * 4


def tiny_spec(program_window=None):
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    config["experiment_args"] = [
        "batch-size:1", "vocab:50", "hidden:64", "kv-heads:2", "head-dim:16", "heads:6,8,8,8,6",
        "dense-width:96", "experts:16", "experts-per-token:4", "expert-width:24",
        "shared-width:24", "experts-held:0-3", "seq:32", "attn-chunk:8",
        "corpus:16", "window:%d" % (program_window or 12)]
    config["image_size"] = dict(
        config["image_size"], sequence_length=32, hidden_size=64, num_key_value_heads=2,
        head_dim=16, num_attention_heads_per_layer=[6, 8, 8, 8, 6], sliding_window=12,
        intermediate_size=96, num_experts=16, num_experts_per_tok=4, moe_intermediate_size=24,
        shared_expert_intermediate_size=24, experts_held=[0, 1, 2, 3])
    config["classes"] = 50
    config["learning_rate_args"] = ["initial-rate:0.5"]
    spec["limits"] = {"reference": {"steps": "all", "precision": "highest"},
                      "limits": {"narrow_products": 0, "loss_gap": 1e-3, "grad_norm_gap": 1e-2,
                                 "dparam_gap": 3e-2}}
    return spec


def compared(capsys):
    return {c["number"]: c for c in (
        json.loads(line.split(" ", 2)[2]) for line in capsys.readouterr().out.splitlines()
        if line.startswith("grid compare {"))}


@pytest.mark.parametrize("program_window,sound", [(None, True), (6, False), (32, False)])
def test_whole_run_off_the_chip(capsys, program_window, sound):
    import jax

    import run

    result = run.run_cell(tiny_spec(program_window), 2 ** 31 + 5, 0.5, False, jax.devices()[:1],
                          device_metrics=False)
    numbers = compared(capsys)
    assert result["correct"] is sound, numbers
    assert result["failed"] == 0 and numbers["narrow_products"]["value"] == 0
