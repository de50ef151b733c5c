"""Pallas TPU kernels for the EAAS hot spots (DESIGN.md §6).

* :mod:`repro.kernels.grouped_gemm` — expert-server grouped GEMM with
  group-shrink (the paper's §4.1 kernel).
* :mod:`repro.kernels.decode_attention` — flash-decode GQA attention,
  dense and paged (K/V gathered through a block table via scalar-prefetch
  index maps).
* :mod:`repro.kernels.combine` — fused top-k combine epilogue.
* :mod:`repro.kernels.ops` — jit wrappers + CPU lowerings.
* :mod:`repro.kernels.ref` — pure-jnp oracles.
"""

from repro.kernels import ops, ref  # noqa: F401
