"""What the process runs on: whether kernels compile for a TPU.  (The chip's
published peaks are the benchmark's: ``grid/peaks.json``, by ``device_kind``.)
"""

import jax


def on_tpu():
    """True when this process's default backend is a TPU — the ONE answer to
    "do the Pallas kernels compile (Mosaic) or interpret, and which GAR tier
    and leaf path serve" (ops/pallas_kernels.py, gars/common.py,
    parallel/engine.py all ask here).  The default backend is where an
    un-pinned ``jit`` lands and what ``pallas_call`` lowers for; a mesh of
    another platform inside a TPU process is not a supported layout."""
    return jax.default_backend() == "tpu"
