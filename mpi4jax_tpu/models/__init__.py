"""Model definitions built on the package's kernels and expert layer.

- ``mla_moe``: a decoder of latent attention (MLA) and sigmoid-routed
  experts with a multi-token-prediction module, and its training step —
  one chip's share of an expert-parallel job (docs/moe.md).
- ``hybrid_moe``: a decoder of gated short convolutions and grouped-query
  attention with sigmoid-routed experts (LFM2-MoE), and its training step,
  the same kind of share.
- ``training``: what the two training steps share (AdamW, the selection
  bias's move, the chunked cross-entropy, the gradient norms by group).
"""
