"""The Keye cell's own pieces: the FLOP function against a count by hand, the
parameters against the configuration's count, the configuration against the
source's keys, the four readers on a recorded table and a recorded trace, and a
whole run off the chip at a tiny size — ``correct`` true for the sound path,
false with the timed path given a fault that reaches only the selection: every
causal key read in place of the chosen ones, or half as many chosen."""

import json

import numpy as np
import pytest

from cell import cell_spec, flops_per_step, load_module

WORKLOAD = "keye_avgmedian_sparse8k"


def test_forward_macs_by_hand():
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    flops = load_module("flops", "keye_vl2")
    projections = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128       # W_q, W_o; W_k, W_v
    indexer = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16             # W_Iq, W_Ik, W_Iw
    assert (projections, indexer) == (18874368, 2260992)         # a layer's matrices, less norms
    selected = 2048 * 2049 // 2 + 6144 * 2048                    # min(t + 1, 2048) keys a query
    assert selected == flops.selected_pairs(8192, 2048) == 14681088
    causal = 8192 * 8193 // 2
    assert round(100 * selected / causal, 1) == 43.7             # of the causal pairs
    experts = 8 * 8 / 128 * 3 * 2048 * 768                       # 0.5 visits of a held expert
    layer = (8192 * (projections + indexer + 2048 * 128 + experts)
             + causal * 16 * 64                                  # the index scores: every causal pair
             + selected * 32 * (128 + 128))                      # scores and values: the selected
    by_hand = int(4 * layer + 8192 * 2048 * 18992)
    assert by_hand == 1715621330944                              # 1.716 T: 209.4 M a position
    assert flops.forward_macs(config["image_size"], config["classes"]) == by_hand
    assert flops_per_step(spec) == 6 * by_hand * 3               # 30.88 TFLOP a step
    attention = 4 * (selected * 32 * 256 + causal * 16 * 64)
    assert round(100 * attention / by_hand) == 36                # selected pairs + index scores
    # the roofline's two functions: 2 + 2 + 5 products a selected pair a head, three passes' tensors
    assert flops.selected_attention_flops(config["image_size"], 3) == (
        2 * 9 * selected * 32 * 128 * 4 * 3)
    wide, narrow = 8192 * 32 * 128 * 4, 8192 * 4 * 128 * 4
    assert flops.selected_attention_bytes(config["image_size"], 3) == (
        (2 * (2 * wide + 2 * narrow) + 4 * wide + 4 * narrow) * 4 * 3)


def test_parameters_are_the_raveled_state():
    import jax

    config = cell_spec(WORKLOAD)["config_data"]
    shapes = jax.eval_shape(lambda key: load_module("references", "keye_vl2").init(
        key, config["image_size"], config["classes"]), jax.random.PRNGKey(0))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) \
        == config["parameters"] == 4 * 59150720 + 77793280 == 305351680 + 4 * 2261120


def test_the_configuration_keeps_the_sources_widths():
    spec = cell_spec(WORKLOAD)
    config, shape = spec["config_data"], spec["config_data"]["image_size"]
    published = {"hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "intermediate_size": 6144, "moe_intermediate_size": 768,
                 "num_experts_per_tok": 8, "num_local_experts": 128, "norm_topk_prob": True,
                 "rope_theta": 10000000, "rms_norm_eps": 1e-6, "model_type": "KeyeVL2",
                 "max_position_embeddings": 262144, "decoder_sparse_step": 1, "mlp_only_layers": [],
                 "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                                  "type": "default"},
                 "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                               "q_chunk_size": 512, "topk": 2048}}
    for key, value in published.items():
        assert config[key] == value, key
        assert key not in shape or shape[key] == value, key
    changed = {"num_hidden_layers": (48, 4), "num_experts": (128, 8), "vocab_size": (151936, 18992)}
    for key, (source, here) in changed.items():
        assert config["published"][key] == source and config[key] == here, key
    manifest = {c["name"]: c for c in spec["manifest"]["configs"]}[spec["config"]]
    assert sorted(manifest["reduced"]) == sorted(config["reduced"]) \
        == sorted(list(changed) + ["batch_per_worker"])
    assert shape["num_experts"] == 128 and len(shape["experts_held"]) == 8
    assert shape["sequence_length"] == 8192 and config["classes"] == 151936 // 8
    assert (config["nb_workers"], config["nb_decl_byz_workers"]) == (3, 1)


def test_the_four_readers_on_a_recorded_table_and_trace():
    """The three part readers sum their parts of the one table
    ``_model_parts.parts`` keeps in the context and read nothing where the
    program named no part; the roofline's reader sums the ``selected_attention_*``
    operations of device 0 inside the step program's spans and reads nothing
    where there are none (the chunked form; the parent)."""
    table = {"parts": {"attention": 150.0, "indexer": 120.5, "select": 300.25, "sparse_attend": 90.0,
                       "router": 9.0, "experts": 100.0, "head": 32.0, "embed": 3.0},
             "unnamed_ms": 70.0}
    read = lambda name, ctx: load_module("layer_metrics", name).read(ctx)
    assert read("indexer_ms_per_step", {"model_parts": table}) == 120.5
    assert read("select_ms_per_step", {"model_parts": table}) == 300.25
    assert read("sparse_attend_ms_per_step", {"model_parts": table}) == 90.0
    names = ("indexer_ms_per_step", "select_ms_per_step", "sparse_attend_ms_per_step",
             "sparse_attend_roofline_pct")
    for name in names[:3]:
        assert read(name, {"model_parts": None}) is None
    spec = cell_spec(WORKLOAD)
    listed = {m["name"]: m for m in spec["manifest"]["per_layer"]}
    for name in names:
        assert listed[name]["workloads"] == [WORKLOAD] and listed[name]["moves"] == "steps_per_s"
        assert listed[name]["layer"] == "model" and listed[name]["source"] == "device_trace"
    assert listed["sparse_attend_roofline_pct"]["unit"] == "%"

    ms = 1_000_000
    ops = [["selected_attention_fwd.7 (f32[3,1,8192,4096]", 10 * ms, 30 * ms],
           ["fusion.3 f32[3,8192,2048]", 40 * ms, 5 * ms],
           ["selected_attention_bwd.4 (f32[3,1,8192,4096]", 50 * ms, 90 * ms],
           ["selected_attention_fwd.8 (f32[3,1,8192,4096]", 150 * ms, 30 * ms],
           ["selected_attention_fwd.7 (f32[3,1,8192,4096]", 400 * ms, 30 * ms]]   # outside the spans
    raw = {"devices": {"0": {"modules": [["jit_step", 0, 200 * ms], ["other", 390 * ms, 50 * ms]],
                             "ops": ops},
                       "1": {"modules": [], "ops": []}}, "host": []}
    ctx = {"trace": {"step_module": "jit_step", "steps_traced": 2}, "raw_trace": raw, "cell": spec,
           "peaks": {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}}
    least_ms = 1e3 * 2 * 9 * 14681088 * 32 * 128 * 4 * 3 / 1.97e14    # 65.93: operations bound it
    assert read("sparse_attend_roofline_pct", ctx) == pytest.approx(100 * least_ms / 75.0)
    raw["devices"]["0"]["ops"] = [op for op in ops if not op[0].startswith("selected_")]
    assert read("sparse_attend_roofline_pct", ctx) is None


TINY_ARGS = ["batch-size:1", "vocab:50", "hidden:64", "heads:8", "kv-heads:2", "head-dim:16",
             "layers:3", "experts:16", "experts-per-token:4", "expert-width:24",
             "experts-held:0-3", "index-heads:4", "index-head-dim:8", "index-topk:8",
             "mrope-section:2,3,3", "seq:32", "attn-chunk:8", "select-chunk:16", "corpus:16"]


def tiny_spec():
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    config["experiment_args"] = list(TINY_ARGS)
    config["image_size"] = dict(
        config["image_size"], sequence_length=32, hidden_size=64, num_attention_heads=8,
        num_key_value_heads=2, head_dim=16, num_hidden_layers=3, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=24, experts_held=[0, 1, 2, 3],
        rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
        sa_config=dict(config["image_size"]["sa_config"], indexer_head_dim=8, indexer_num_heads=4,
                       topk=8))
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


@pytest.mark.parametrize("fault,sound", [((), True), (("index-topk:32",), False),
                                         (("index-topk:4",), False)],
                         ids=["sound", "every-causal-key", "half-the-keys"])
def test_whole_run_off_the_chip(capsys, fault, sound):
    """The planted faults are experiment arguments handed to the timed path
    alone (``Cell(spec, devices, extra_experiment_args=...)``), as on the chip.
    The seeded weights are drawn ten times wider than the reference's 0.02: at
    hidden 64 the scores are otherwise so small that attention is uniform and
    no choice of keys can be told from another."""
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
