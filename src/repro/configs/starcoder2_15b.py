"""starcoder2-15b [dense] — 40L d_model=6144 48H (GQA kv=4) d_ff=24576
vocab=49152.  GQA + RoPE (theta 1e5), LayerNorm with bias, a bias on every
linear, GELU-tanh non-gated FFN, untied head.  [arXiv:2402.19173]

The published model attends over a 4,096-position sliding window; this
config has no window (every position attends the whole prefix), which is
the same attention at up to 4,096 positions.

Quantization plan: MXFP4 (FP4xBF16+BF16 MACs, UE8M0 scales).
"""
from repro.models.transformer import ModelConfig

FULL = ModelConfig(
    name="starcoder2-15b", family="dense",
    n_layers=40, d_model=6144, n_heads=48, n_kv_heads=4, d_head=128,
    d_ff=24_576, vocab=49_152,
    activation="gelu", gated_ffn=False, tie_embeddings=False,
    norm="layer", rope_theta=100_000.0, use_bias=True,
    scheme_proj="mxfp4", scheme_ffn="mxfp4",
    microbatches=2,
)

SMOKE = ModelConfig(
    name="starcoder2-smoke", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
    d_ff=128, vocab=512,
    activation="gelu", gated_ffn=False, tie_embeddings=False,
    norm="layer", rope_theta=100_000.0, use_bias=True,
    scheme_proj="mxfp4", scheme_ffn="mxfp4",
    kv_chunk=64,
)
