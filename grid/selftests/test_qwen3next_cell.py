"""The Qwen3-Next cell's own pieces: the FLOP function and the delta rule's two
roofline functions against counts by hand, the parameters against the
configuration's count, the configuration against the source's keys, the four
readers on a recorded table and a recorded trace, and a whole run off the chip
at a tiny stand-in — ``correct`` true for the sound path, false with the timed
path given a fault that reaches only the recurrence: the decay dropped, or no
state carried from one chunk to the next."""

import json
import os
import sys

import numpy as np
import pytest

from cell import ROOT, cell_spec, flops_per_step, load_module

WORKLOAD = "qwen3next_avgmedian_causal4k"


def test_forward_macs_by_hand():
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    flops = load_module("flops", "qwen3_next")
    delta = (2048 * 12288 + 2048 * 64          # W_qkvz, W_ba
             + 8192 * 4 + 4096 * 2048)         # the convolution's taps, W_o
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048            # W_q with its gate, W_k, W_v, W_o
    assert (delta, full) == (33718272, 27262976)                 # a mixer's leaves, less its norms
    ffn = 2048 * 512 + 2048 + 3 * 2048 * 512 + 10 * 8 / 512 * 3 * 2048 * 512   # 0.15625 visits
    # one chunk of one head: 2,016 pairs under the diagonal, 2,080 on or under it
    chunk = 2016 * 128 + 2016 * 256 + 2080 * 128 + 2080 * 128 + 3 * 64 * 128 * 128
    assert chunk == flops.delta_rule_chunk_macs(64, 128, 128) == 4452352
    rule = 64 * 32 * chunk                                       # 64 chunks, 32 value heads
    assert rule == flops.delta_rule_macs(config["image_size"]) == 9118416896
    causal = 4096 * 4097 // 2
    by_hand = int(3 * (4096 * (delta + ffn) + rule) + 4096 * (full + ffn) + 2 * causal * 16 * 256
                  + 4096 * 2048 * 18992)
    assert by_hand == 858213318656                               # 0.858 T: 209.5 M a position
    assert flops.forward_macs(config["image_size"], config["classes"]) == by_hand
    assert flops_per_step(spec) == 6 * by_hand * 3               # 15.45 TFLOP a step
    assert round(100 * 3 * rule / by_hand, 1) == 3.2             # the delta rule's share of the MACs
    # the roofline's two functions: four passes' products; q, k at 16 heads, v, o, g, beta
    assert flops.delta_rule_flops(config["image_size"], 3) == 2 * 4 * rule * 3 * 3
    forward = 4096 * (2 * 2048 + 2 * 4096 + 64) * 4
    assert flops.delta_rule_bytes(config["image_size"], 3) == (
        (2 * forward + 2 * forward - 4096 * 4096 * 4) * 3 * 3)
    least_ms = 1e3 * flops.delta_rule_bytes(config["image_size"], 3) / 8.19e11
    assert round(least_ms, 2) == 8.16                            # bytes bound it; operations 3.33


def test_parameters_are_the_raveled_state():
    import jax

    config = cell_spec(WORKLOAD)["config_data"]
    shapes = jax.eval_shape(lambda key: load_module("references", "qwen3_next").init(
        key, config["image_size"], config["classes"]), jax.random.PRNGKey(0))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) \
        == config["parameters"] == 3 * 33718464 + 27263488 + 4 * 29366272 + 77793280


def test_the_configuration_keeps_the_sources_widths():
    spec = cell_spec(WORKLOAD)
    config, shape = spec["config_data"], spec["config_data"]["image_size"]
    published = {"hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
                 "num_key_value_heads": 2, "full_attention_interval": 4, "partial_rotary_factor": 0.25,
                 "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
                 "linear_num_value_heads": 32, "linear_value_head_dim": 128,
                 "intermediate_size": 5120, "moe_intermediate_size": 512,
                 "shared_expert_intermediate_size": 512, "num_experts_per_tok": 10,
                 "norm_topk_prob": True, "rope_theta": 10000000, "rope_scaling": None,
                 "rms_norm_eps": 1e-6, "model_type": "qwen3_next", "hidden_act": "silu",
                 "max_position_embeddings": 262144, "decoder_sparse_step": 1, "mlp_only_layers": [],
                 "tie_word_embeddings": False, "use_sliding_window": False}
    for key, value in published.items():
        assert config[key] == value, key
        assert key not in shape or shape[key] == value, key
    changed = {"num_hidden_layers": (48, 4), "num_experts": (512, 8), "vocab_size": (151936, 18992)}
    for key, (source, here) in changed.items():
        assert config["published"][key] == source and config[key] == here, key
    manifest = {c["name"]: c for c in spec["manifest"]["configs"]}[spec["config"]]
    assert sorted(manifest["reduced"]) == sorted(config["reduced"]) \
        == sorted(list(changed) + ["batch_per_worker"])
    assert shape["num_experts"] == 512 and len(shape["experts_held"]) == 8
    assert shape["sequence_length"] == 4096 and config["classes"] == 151936 // 8
    assert (config["nb_workers"], config["nb_decl_byz_workers"]) == (3, 1)
    assert "64" in config["deployment"] or "sixty-four" in config["deployment"]
    assert spec["chips"] == 1 and spec["traffic"] == "avgmedian_causal_sampled"


def test_the_four_readers_on_a_recorded_table_and_trace():
    """The three part readers sum their parts of the one table
    ``_model_parts.parts`` keeps in the context; the roofline's reader divides the
    least time by the ``delta_rule`` part's; all four read nothing where the
    program named no part or no ``delta_rule`` scope (another family; the
    parent)."""
    table = {"parts": {"gdn_project": 180.5, "delta_rule": 220.0, "attention_full": 48.25,
                       "router": 20.0, "experts": 84.0, "shared_expert": 11.5, "head": 38.0,
                       "embed": 4.0}, "unnamed_ms": 32.0}
    read = lambda name, ctx: load_module("layer_metrics", name).read(ctx)
    spec = cell_spec(WORKLOAD)
    ctx = {"model_parts": table, "cell": spec,
           "peaks": {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}}
    assert read("delta_rule_ms_per_step", ctx) == 220.0
    assert read("gdn_project_ms_per_step", ctx) == 180.5
    assert read("gated_attention_ms_per_step", ctx) == 48.25
    least_ms = 1e3 * (4 * 4096 * 12352 * 4 - 4096 * 4096 * 4) * 9 / 8.19e11
    assert read("delta_rule_roofline_pct", ctx) == pytest.approx(100 * least_ms / 220.0)
    assert 3.5 < read("delta_rule_roofline_pct", ctx) < 3.9
    names = ("delta_rule_ms_per_step", "gdn_project_ms_per_step", "gated_attention_ms_per_step",
             "delta_rule_roofline_pct")
    for name in names:
        assert read(name, dict(ctx, model_parts=None)) is None
    other = {"parts": {"attention_full": 54.0, "experts": 100.0}, "unnamed_ms": 1.0}   # no such scope
    assert read("delta_rule_roofline_pct", dict(ctx, model_parts=other)) is None
    listed = {m["name"]: m for m in spec["manifest"]["per_layer"]}
    for name in names:
        assert listed[name]["workloads"] == [WORKLOAD] and listed[name]["moves"] == "steps_per_s"
        assert listed[name]["layer"] == "model" and listed[name]["source"] == "device_trace"
    assert listed["delta_rule_roofline_pct"]["unit"] == "%"

    # from a recorded trace: operations of device 0 inside the step program's spans, booked to
    # the part their instruction's scope names (_model_parts.parts through a stand-in table)
    ms = 1_000_000
    ops = [["fusion.7 f32[3,32,64,64,128]", 10 * ms, 30 * ms], ["while.3", 50 * ms, 90 * ms],
           ["fusion.9 f32[3,4096,12288]", 150 * ms, 40 * ms], ["fusion.7 f32[3,32,64,64,128]", 400 * ms, 30 * ms]]
    raw = {"devices": {"0": {"modules": [["jit_step", 0, 200 * ms], ["other", 390 * ms, 50 * ms]],
                             "ops": ops}}, "host": []}
    import layer_metrics._model_parts as model_parts
    import phase_reduce

    booked = {"fusion.7": "delta_rule", "while.3": "delta_rule", "fusion.9": "gdn_project"}
    traced = {"trace": {"step_module": "jit_step", "steps_traced": 2}, "raw_trace": raw, "cell": spec,
              "peaks": ctx["peaks"]}
    sound = model_parts.program_table, model_parts.phases
    model_parts.program_table = lambda *_: (booked, [], 0.0)
    model_parts.phases = lambda ctx: None
    try:
        assert phase_reduce.instruction("fusion.7 f32[3,32,64,64,128]") == "fusion.7"
        assert read("delta_rule_ms_per_step", traced) == 60.0      # 120 ms inside the spans, 2 steps
        assert read("gdn_project_ms_per_step", traced) == 20.0
        assert read("gated_attention_ms_per_step", traced) == 0.0
        assert read("delta_rule_roofline_pct", traced) == pytest.approx(100 * least_ms / 60.0)
        booked.update({"fusion.7": "attention_full", "while.3": None})   # a program without the scope
        del traced["model_parts"]
        assert read("delta_rule_roofline_pct", traced) is None
    finally:
        model_parts.program_table, model_parts.phases = sound


TINY_ARGS = ["batch-size:1", "vocab:50", "hidden:64", "layers:4", "heads:4", "kv-heads:2",
             "head-dim:16", "key-heads:2", "value-heads:4", "key-dim:16", "value-dim:16", "chunk:8",
             "experts:16", "experts-per-token:4", "expert-width:24", "shared-width:24",
             "experts-held:0-3", "seq:32", "attn-chunk:8", "corpus:16"]


def tiny_spec():
    spec = cell_spec(WORKLOAD)
    config = spec["config_data"]
    config["experiment_args"] = list(TINY_ARGS)
    config["image_size"] = dict(
        config["image_size"], sequence_length=32, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16, delta_chunk=8, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=24, shared_expert_intermediate_size=24,
        experts_held=[0, 1, 2, 3])
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


@pytest.mark.parametrize("fault,sound", [(None, True), ("no-decay", False), ("no-carry", False)],
                         ids=["sound", "no-decay", "no-carry"])
def test_whole_run_off_the_chip(capsys, fault, sound):
    """The cell builds at a tiny stand-in (the reference and the experiment
    agree on the parameters' shapes, or ``Cell`` refuses), runs its two-step
    dispatch and is held to the reference's recurrence.  The planted faults are
    scripts/gdn_layer_check.py's, in force while the timed path is traced, as on
    the chip.  The seeded matrices are drawn ten times wider than the
    reference's 0.02: at hidden 64 the gates and keys are otherwise so near
    uniform that no state can be told from another."""
    import jax

    import run
    from cell import Cell

    scripts = os.path.join(ROOT, "scripts")
    sys.path.insert(0, scripts)
    try:
        import gdn_layer_check
    finally:
        sys.path.remove(scripts)

    def make_cell(spec, devices):
        cell = Cell(spec, devices)
        config = spec["config_data"]
        cell.reference.INIT_STD = 0.2
        cell._init = jax.jit(lambda key: cell.reference.init(
            key, config["image_size"], config["classes"]))
        return cell

    with gdn_layer_check.planted(fault):
        result = run.run_cell(tiny_spec(), 2 ** 31 + 7, 0.5, False, jax.devices()[:1],
                              device_metrics=False, make_cell=make_cell)
    sys.modules.pop("gdn_layer_check", None)
    numbers = compared(capsys)
    assert result["correct"] is sound, numbers
    assert result["failed"] == 0 and numbers["narrow_products"]["value"] == 0
