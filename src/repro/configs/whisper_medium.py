"""Whisper-medium [arXiv:2212.04356] — encoder-decoder, conv frontend stub.

24L (encoder) + 24L (decoder), d_model=1024, 16H MHA, d_ff=4096, vocab=51865.
mel+conv codec is a STUB: input_specs hands 1500 precomputed frame embeddings.
Plain (non-gated) GELU MLP as in the original.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="whisper-medium",
    family="audio",
    num_layers=24,          # decoder depth
    encoder_layers=24,
    encoder_seq=1500,       # 30 s of audio at 50 Hz after conv stride
    d_model=1024,
    d_ff=4096,
    vocab_size=51865,
    num_heads=16,
    num_kv_heads=16,
    head_dim=64,
    mlp_act="gelu",
    source="arXiv:2212.04356",
)
