"""Model definitions built on the package's kernels and expert layer.

- ``mla_moe``: a decoder of latent attention (MLA) and sigmoid-routed
  experts with a multi-token-prediction module, and its training step —
  one chip's share of an expert-parallel job (docs/moe.md).
"""
