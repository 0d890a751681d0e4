"""The least time the chip could take for a step's chunked gated delta rule,
over the time the step spent in it: the larger of the rule's necessary
operations over the bf16 peak and of q, k, v, g, beta, the output and their
gradients over the HBM peak (grid/flops/qwen3_next.py ``delta_rule_flops`` and
``delta_rule_bytes``: forward, the checkpointed layers' recomputed forward and
backward, every value head, DeltaNet layer and worker; grid/peaks.json), over
the self time a step of EVERY operation inside the ``model.delta_rule`` scope
(_model_parts.py's part ``delta_rule``, ``delta_rule_ms_per_step``'s number).
It reads the scope and not a kernel's name: XLA's fusions and loops today, a
Pallas kernel with the copies round it tomorrow, judged by the same yardstick;
whatever runs there does at least the counted work, so the share cannot pass
100.  A program that names no such part (another family; the parent) gives
nothing to read."""

from cell import load_module
from layer_metrics._model_parts import parts


def read(ctx):
    found = parts(ctx)
    took_ms = found and found["parts"].get("delta_rule")
    if not took_ms:
        return None
    config = ctx["cell"]["config_data"]
    flops = load_module("flops", config["family"])
    peak, shape, workers = ctx["peaks"], config["image_size"], config["nb_workers"]
    by_flops = flops.delta_rule_flops(shape, workers) / peak["bf16_flops_per_s"]
    by_bytes = flops.delta_rule_bytes(shape, workers) / peak["hbm_bytes_per_s"]
    print("grid delta_rule_roofline: least %.4f ms by %s (operations %.4f ms, bytes %.4f ms) "
          "over %.4f ms inside model.delta_rule a step" % (
              1e3 * max(by_flops, by_bytes), "operations" if by_flops >= by_bytes else "bytes",
              1e3 * by_flops, 1e3 * by_bytes, took_ms), flush=True)
    return 100.0 * 1e3 * max(by_flops, by_bytes) / took_ms
