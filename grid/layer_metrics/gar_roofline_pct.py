"""The least time the chip could take for the rule's call over the time it
took: the larger of the bytes the algorithm must move over the HBM peak and its
operations over the bf16 peak (grid/rules/<rule>.py, grid/peaks.json)."""

from cell import load_module


def read(ctx):
    probe, peak = ctx["gar_probe"], ctx["peaks"]
    rule = load_module("rules", ctx["cell"]["traffic_data"]["aggregator"])
    n, f, d = probe["n"], probe["f"], probe["d"]
    by_bytes = rule.least_bytes(n, f, d) / peak["hbm_bytes_per_s"]
    by_flops = rule.flops(n, f, d) / peak["bf16_flops_per_s"]
    print("grid gar_roofline: least %.4f ms by %s (bytes %.4f ms, operations %.4f ms)"
          % (1e3 * max(by_bytes, by_flops), "bytes" if by_bytes >= by_flops else "operations",
             1e3 * by_bytes, 1e3 * by_flops), flush=True)
    return 100.0 * 1e3 * max(by_bytes, by_flops) / probe["device_ms"]
