"""What the process runs on: the accelerator's published peaks, and whether
kernels compile for a TPU.

One table keyed by ``device_kind`` (what ``jax.devices()[0].device_kind``
reports), each row with its source; a kind that is not in it raises — a
utilization stated against another chip's peak is worse than none
(consumers: bench.py, benchmarks/opt_sweep.py, benchmarks/mfu_probe.py).
"""

import collections

import jax

Peaks = collections.namedtuple("Peaks", "bf16_flops hbm_bytes_per_s source")

#: Per-chip peaks by ``device_kind``.
PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=1.97e14,
        hbm_bytes_per_s=8.19e11,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM2e at 819 GB/s per chip",
    ),
}


def peaks(device):
    """Published peaks of ``device`` (a ``jax.Device``); raises ``KeyError``
    naming the kind when the table has no row for it."""
    kind = device.device_kind
    if kind not in PEAKS:
        raise KeyError(
            "no published peaks for device_kind %r (platform %r): add a "
            "sourced row to aggregathor_tpu/utils/hw.py PEAKS; known kinds: %s"
            % (kind, device.platform, ", ".join(sorted(PEAKS)))
        )
    return PEAKS[kind]


def on_tpu():
    """True when this process's default backend is a TPU — the ONE answer to
    "do the Pallas kernels compile (Mosaic) or interpret, and which GAR tier
    and leaf path serve" (ops/pallas_kernels.py, gars/common.py,
    parallel/engine.py all ask here).  The default backend is where an
    un-pinned ``jit`` lands and what ``pallas_call`` lowers for; a mesh of
    another platform inside a TPU process is not a supported layout."""
    return jax.default_backend() == "tpu"
