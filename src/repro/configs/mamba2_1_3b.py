"""Mamba2-1.3B — SSD (state-space duality), attention-free.

[arXiv:2405.21060; https://huggingface.co/state-spaces/mamba2-1.3b,
config.json, with the ``Mamba2`` mixer's defaults]  48L d_model=2048,
d_intermediate=0 (no MLP), no attention layers; d_state=128, headdim 64,
expand 2 (d_inner 4096, 64 heads), ngroups 1, d_conv 4, chunk 256.
RMSNorm eps 1e-5 (pre-norm, the gated ``norm(y * silu(z))`` and the
final norm), residual in float32, tied embedding; vocab_size 50277
padded to a multiple of 16: 50288 rows.

This is the architecture where the paper's shuffle synthesis applies
most directly: the width-4 depthwise causal conv1d is a sequence
stencil served by the Pallas shuffle-reuse kernel
(repro.kernels.conv1d), with deltas found by the PTXASW analysis.
"""

from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="mamba2-1.3b",
    family="ssm",
    n_layers=48,
    d_model=2048,
    n_heads=0,
    n_kv_heads=0,
    d_ff=0,
    vocab=50288,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    conv_width=4,
    ssm_chunk=256,
    ssm_norm_eps=1e-5,
    norm="rmsnorm",
    rope_theta=0.0,
    ssm_mm_dtype="compute",
    source="https://huggingface.co/state-spaces/mamba2-1.3b",
    notes="attention-free; long_500k runs (O(1) decode state)",
))
