"""Kernel tiers below the jnp/XLA default.

- ``ops.native`` — C++17 host library (threadpool + GAR kernels) loaded via
  ctypes; the framework's equivalent of the reference's native op layer
  (native/__init__.py, aggregators/deprecated_native/) for host-side
  aggregation, oracles at scale, and environments without an accelerator.
- ``ops.pallas_kernels`` — hand-written Pallas TPU kernels for the GAR hot
  path (pairwise distances, coordinate-wise selection), replacing the
  reference's CUDA/custom-op tier (native/op_krum, native/op_bulyan).
- ``ops.attention`` — masked attention as one fused Pallas kernel, forward and
  backward, with its chooser (the kernel on a TPU for shapes it takes, the
  caller's XLA form elsewhere); models/laguna.py's attention calls it.
- ``ops.select`` — the ``topk`` keys of largest score a query chosen by
  counting, one Pallas kernel; models/keye_vl2.py's ``top_keys`` calls it.
- ``ops.delta_rule`` — the chunked gated delta rule as a Pallas kernel pair,
  forward and backward behind one ``custom_vjp``, the state a head in VMEM
  across a sequence's chunks; models/qwen3_next.py's ``delta_rule`` calls it.
- ``ops.gdn_operands`` — a Gated DeltaNet layer's q, k, v from the projection's
  output in one pass (the causal convolution, SiLU, the heads' L2 norm, the
  repeat to the value heads), a Pallas kernel pair behind one ``custom_vjp``;
  models/qwen3_next.py's ``delta_heads`` calls it.
"""
