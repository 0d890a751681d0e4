"""The Kanana cell's own pieces: the FLOP function against a count by hand, the
parameters against the configuration's count, the configuration against the
source's keys, the three part readers on a recorded table, and a whole run off
the chip at a tiny size — ``correct`` true for the sound path, false with the
timed path given a fault that reaches only a new mechanism: another rotary
base, or two more experts a token than the reference routes to."""

import json

import numpy as np
import pytest

from cell import cell_spec, flops_per_step, load_module

WORKLOAD = "kanana_avgmedian_causal4k"


def test_forward_macs_by_hand():
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    flops = load_module("flops", "deepseek_v3")
    projections = (2048 * 16 * 192 + 2048 * (512 + 64)      # W_q at 16 heads, W_kva whole
                   + 512 * 16 * (128 + 128) + 16 * 128 * 2048)  # W_kvb, W_o
    assert projections == 13762560                           # the layer's matrices, less its norm
    pairs = 4096 * 4097 // 2
    attention = 4096 * projections + pairs * 16 * (192 + 128)   # scores over 192, values over 128
    sparse = 2048 * 128 + 3 * 2048 * 1536 + 6 * 8 / 128 * 3 * 2048 * 768  # router, shared, 0.375 visits
    by_hand = int(5 * attention + 4096 * (3 * 2048 * 6144 + 4 * sparse) + 4096 * 2048 * 16032)
    assert by_hand == 973667827712                           # 0.974 T: 237.7 M a position
    assert flops.forward_macs(config["image_size"], config["classes"]) == by_hand
    assert flops_per_step(spec) == 6 * by_hand * 3           # 17.53 TFLOP a step
    assert round(100 * 5 * attention / by_hand) == 51        # latent attention's share of the MACs


def test_parameters_are_the_raveled_state():
    import jax

    config = cell_spec(WORKLOAD)["config_data"]
    shapes = jax.eval_shape(lambda key: load_module("references", "deepseek_v3").init(
        key, config["image_size"], config["classes"]), jax.random.PRNGKey(0))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) \
        == config["parameters"] == 362045952 + 4 * 128       # the issue's count and four biases


def test_the_configuration_keeps_the_sources_widths():
    spec = cell_spec(WORKLOAD)
    config, shape = spec["config_data"], spec["config_data"]["image_size"]
    published = {"hidden_size": 2048, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "qk_head_dim": 192, "v_head_dim": 128, "kv_lora_rank": 512, "q_lora_rank": None,
                 "intermediate_size": 6144, "moe_intermediate_size": 768, "num_experts_per_tok": 6,
                 "n_shared_experts": 2, "routed_scaling_factor": 2.448, "rope_theta": 1000000,
                 "first_k_dense_replace": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "rope_interleave": True,
                 "rope_scaling": None, "rms_norm_eps": 1e-6, "model_type": "deepseek_v3"}
    for key, value in published.items():
        assert config[key] == value, key
        assert key not in shape or shape[key] == value, key
    changed = {"num_hidden_layers": (48, 5), "n_routed_experts": (128, 8),
               "vocab_size": (128256, 16032), "num_attention_heads": (32, 16),
               "num_key_value_heads": (32, 16)}
    for key, (source, here) in changed.items():
        assert config["published"][key] == source and config[key] == here, key
    manifest = {c["name"]: c for c in spec["manifest"]["configs"]}[spec["config"]]
    assert sorted(manifest["reduced"]) == sorted(config["reduced"]) \
        == sorted(list(changed) + ["batch_per_worker"])
    assert shape["n_routed_experts"] == 128 and len(shape["experts_held"]) == 8
    assert shape["num_attention_heads"] == 16 and config["classes"] == 128256 // 8


def test_the_three_readers_on_a_recorded_table():
    """Each reader sums its parts of the one table ``_model_parts.parts`` keeps
    in the context, and reads nothing where the program named no part."""
    table = {"parts": {"mla_attend": 90.5, "mla_project": 60.25, "dense_mlp": 30.0, "router": 9.0,
                       "experts": 100.0, "shared_expert": 20.5, "head": 32.0, "embed": 3.0},
             "unnamed_ms": 70.0}
    read = lambda name, ctx: load_module("layer_metrics", name).read(ctx)
    assert read("latent_attend_ms_per_step", {"model_parts": table}) == 90.5
    assert read("latent_project_ms_per_step", {"model_parts": table}) == 60.25
    assert read("ffn_ms_per_step", {"model_parts": table}) == 159.5
    for name in ("latent_attend_ms_per_step", "latent_project_ms_per_step", "ffn_ms_per_step"):
        assert read(name, {"model_parts": None}) is None
    listed = {m["name"]: m for m in cell_spec(WORKLOAD)["manifest"]["per_layer"]}
    for name in ("latent_attend_ms_per_step", "latent_project_ms_per_step"):
        assert listed[name]["workloads"] == [WORKLOAD] and listed[name]["moves"] == "steps_per_s"
    # one reader for the feed-forward layers of both models that name these parts (PR 42)
    assert listed["ffn_ms_per_step"]["workloads"] == ["laguna_avgmedian_causal4k", WORKLOAD]
    assert "routed_ffn_ms_per_step" not in listed


TINY_ARGS = ["batch-size:1", "vocab:50", "hidden:64", "heads:8", "heads-held:4",
             "qk-nope-head-dim:16", "qk-rope-head-dim:8", "v-head-dim:16", "kv-lora-rank:24",
             "layers:3", "dense-width:96", "experts:16", "experts-per-token:4", "expert-width:24",
             "experts-held:0-3", "seq:32", "attn-chunk:8", "corpus:16"]


def tiny_spec():
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    config["experiment_args"] = list(TINY_ARGS)
    config["image_size"] = dict(
        config["image_size"], sequence_length=32, hidden_size=64, num_attention_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24,
        num_hidden_layers=3, intermediate_size=96, n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=24, experts_held=[0, 1, 2, 3])
    config["classes"] = 50
    config["learning_rate_args"] = ["initial-rate:0.05"]
    spec["limits"] = {"reference": {"steps": "all", "precision": "highest"},
                      "limits": {"narrow_products": 0, "loss_gap": 1e-3, "grad_norm_gap": 1e-2,
                                 "dparam_gap": 3e-2}}
    return spec


def compared(capsys):
    return {c["number"]: c for c in (
        json.loads(line.split(" ", 2)[2]) for line in capsys.readouterr().out.splitlines()
        if line.startswith("grid compare {"))}


@pytest.mark.parametrize("fault,sound", [((), True), (("rope-theta:10",), False),
                                         (("experts-per-token:6",), False)],
                         ids=["sound", "rotary-base", "two-more-experts"])
def test_whole_run_off_the_chip(capsys, fault, sound):
    """The planted faults are experiment arguments handed to the timed path
    alone (``Cell(spec, devices, extra_experiment_args=...)``), as on the chip.
    The seeded weights are drawn ten times wider than the reference's 0.02: at
    hidden 64 the scores are otherwise so small that attention is uniform and
    no rotary base can be told from another."""
    import jax

    import run
    from cell import Cell

    def make_cell(spec, devices):
        cell = Cell(spec, devices, extra_experiment_args=fault)
        config = spec["config_data"]
        cell.reference.INIT_STD = 0.2
        cell._init = jax.jit(lambda key: cell.reference.init(
            key, config["image_size"], config["classes"]))
        return cell

    spec = tiny_spec()
    spec["config_data"]["experiment_args"] = [
        argument for argument in TINY_ARGS if argument.split(":")[0] not in
        {given.split(":")[0] for given in fault}]
    result = run.run_cell(
        spec, 2 ** 31 + 7, 0.5, False, jax.devices()[:1], device_metrics=False, make_cell=make_cell)
    numbers = compared(capsys)
    assert result["correct"] is sound, numbers
    assert result["failed"] == 0 and numbers["narrow_products"]["value"] == 0
