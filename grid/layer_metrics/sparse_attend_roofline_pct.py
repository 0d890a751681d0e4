"""The least time the chip could take for a step's attention over the SELECTED
pairs, over the time its kernels took: the larger of the operations the
selected pairs need over the bf16 peak and of the bytes q, k, v, the output and
the gradients are over the HBM peak (grid/flops/keye_vl2.py: forward, the
checkpointed layers' recomputed forward and backward, every layer and worker;
grid/peaks.json), over the self time a step of the device operations named
``selected_attention_*`` (ops/attention.py's two calls under a mask that is
data) on device 0 inside the step program's spans.  The work is the selection's,
whatever computes it: a kernel that also folds the causal pairs no query
selected spends time on them and does not count them, so the share cannot pass
100.  A program without those kernels (the chunked XLA form; the parent) gives
nothing to read."""

from cell import load_module
from trace_reduce import _union

KERNELS = "selected_attention_"


def read(ctx):
    reduced, raw = ctx["trace"], ctx["raw_trace"]
    lines = raw["devices"][min(raw["devices"], key=int)]
    spans = _union([start, start + duration] for name, start, duration in lines["modules"]
                   if name == reduced["step_module"])
    if not spans:
        return None
    took_ns = sum(op[2] for op in lines["ops"] if op[0].startswith(KERNELS)
                  and op[1] >= spans[0][0] and op[1] + op[2] <= spans[-1][1])
    if not took_ns:
        return None
    config = ctx["cell"]["config_data"]
    flops = load_module("flops", config["family"])
    peak, shape, workers = ctx["peaks"], config["image_size"], config["nb_workers"]
    by_flops = flops.selected_attention_flops(shape, workers) / peak["bf16_flops_per_s"]
    by_bytes = flops.selected_attention_bytes(shape, workers) / peak["hbm_bytes_per_s"]
    took_ms = took_ns / reduced["steps_traced"] / 1e6
    print("grid sparse_attend_roofline: least %.4f ms by %s (operations %.4f ms, bytes %.4f ms) "
          "over %.4f ms of kernels a step" % (
              1e3 * max(by_flops, by_bytes), "operations" if by_flops >= by_bytes else "bytes",
              1e3 * by_flops, 1e3 * by_bytes, took_ms), flush=True)
    return 100.0 * 1e3 * max(by_flops, by_bytes) / took_ms
