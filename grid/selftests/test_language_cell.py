"""The language-model cell's own pieces: the FLOP function against a count by
hand, the plain median against the program's, and a whole run off the chip at
a tiny size — ``correct`` true for the sound path, false with the timed path
told the wrong experts (it holds 4-7's weights as if they were 0-3's: the
held-expert mask dropped)."""

import json

import numpy as np
import pytest

from cell import cell_spec, flops_per_step, load_module

WORKLOAD = "sdar30b_median_blockdiff"


def test_forward_macs_by_hand():
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    per_position = (2 * 2048 * 4096 + 2 * 2048 * 512      # q and o, k and v
                    + 2048 * 128                            # router
                    + 8 * 8 / 128 * 3 * 2048 * 768)         # half an expert visit
    blocks = 2048 // 4
    pairs = 16 * blocks + 16 * blocks * (blocks - 1) // 2 + 16 * blocks * (blocks + 1) // 2
    assert pairs == 4202496  # a quarter of 4096^2, and the block diagonal
    layer = 4096 * per_position + 2 * pairs * 32 * 128
    by_hand = 4 * layer + 2048 * 2048 * 18992
    assert by_hand == 569552928768
    assert load_module("flops", "sdar_moe").forward_macs(
        config["image_size"], config["classes"]) == by_hand
    assert flops_per_step(spec) == 6 * by_hand * 4  # 13.67 TFLOP a step


def test_parameters_are_the_raveled_state():
    import jax

    config = cell_spec(WORKLOAD)["config_data"]
    shapes = jax.eval_shape(lambda key: load_module("references", "sdar_moe").init(
        key, config["image_size"], config["classes"]), jax.random.PRNGKey(0))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) \
        == config["parameters"] == 305351680


def test_plain_median_is_the_programs():
    import jax.numpy as jnp

    from aggregathor_tpu import gars

    rows = np.random.default_rng(0).normal(size=(4, 1000)).astype(np.float32)
    rows[1, 100:300] = 0.0          # a worker no token of which reached an expert
    rows[2, 200:400] = 0.0
    rows[3, 5] = np.nan
    rows[0, 6] = np.inf
    rule = load_module("rules", "median")
    ours = np.asarray(gars.instantiate("median", 4, 1).aggregate(jnp.asarray(rows)))
    plain = np.asarray(rule.aggregate(jnp.asarray(rows), 1))
    assert np.array_equal(ours, plain)
    assert np.array_equal(plain[250:300], np.sort(rows[:, 250:300], axis=0)[2])
    assert rule.least_bytes(4, 1, 10) == 5 * 10 * 4 and rule.flops(4, 1, 10) == 60


def tiny_spec(program_held="0-3"):
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    config["experiment_args"] = [
        "batch-size:1", "vocab:50", "hidden:64", "heads:8", "kv-heads:2", "head-dim:16",
        "layers:2", "experts:16", "experts-per-token:4", "expert-width:24",
        "experts-held:" + program_held, "seq:32", "block:4", "attn-chunk:16",
        "corpus:16"]
    config["image_size"] = {
        "sequence_length": 32, "block_length": 4, "mask_token_id": 49, "num_hidden_layers": 2,
        "experts_held": [0, 1, 2, 3], "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 24, "rope_theta": 1000000, "rms_norm_eps": 1e-06}
    config["classes"], config["augment"] = 50, "mask_token_id=49"
    config["learning_rate_args"] = ["initial-rate:0.5"]
    spec["traffic_data"]["unroll"] = 2
    spec["limits"] = {"reference": {"steps": "all", "precision": "highest"},
                      "limits": {"narrow_products": 0, "loss_gap": 1e-3, "grad_norm_gap": 1e-2,
                                 "dparam_gap": 1e-2}}
    return spec


def compared(capsys):
    return {c["number"]: c for c in (
        json.loads(line.split(" ", 2)[2]) for line in capsys.readouterr().out.splitlines()
        if line.startswith("grid compare {"))}


@pytest.mark.parametrize("program_held,sound", [("0-3", True), ("4-7", False)])
def test_whole_run_off_the_chip(capsys, program_held, sound):
    import jax

    import run

    result = run.run_cell(tiny_spec(program_held), 2 ** 31 + 5, 0.5, False, jax.devices()[:1],
                          device_metrics=False)
    numbers = compared(capsys)
    assert result["correct"] is sound, numbers
    assert result["failed"] == 0 and numbers["narrow_products"]["value"] == 0
    if not sound:  # the experts' leaves moved by other gradients than the reference's
        assert not numbers["dparam_gap"]["within"]
