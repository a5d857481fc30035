"""Minitron-8B: Nemotron-4 15B pruned and distilled [arXiv:2407.14679],
published as ``nvidia/Minitron-8B-Base``.

32 layers, d_model 4096, 48 query heads of 128 (the pruning cut the
embedding from 6144 to 4096 and kept the heads, so the query width 6144
is not d_model), 8 KV heads (GQA), a non-gated squared-ReLU MLP of
16384, LayerNorm1p (scale = weight + 1, eps 1e-5), RoPE theta 10000 on
the leading half of each head's dims, no linear biases, untied input
and output embeddings, vocabulary 256,000, context 4096. Trained in
bfloat16 with float32 master weights (Megatron / NeMo). Dense full
attention; long_500k runs via the beyond-paper SWA serving variant
(window 4096).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="minitron-8b",
    family="dense",
    num_layers=32,
    d_model=4096,
    d_ff=16384,
    vocab_size=256000,
    num_heads=48,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=10_000.0,
    rotary_frac=0.5,
    mlp_act="relu2",
    norm="layernorm1p",
    dtype="bfloat16",
    param_dtype="float32",
    train_fsdp=True,
    source="arXiv:2407.14679; huggingface.co/nvidia/Minitron-8B-Base",
)

# beyond-paper long-context serving variant (sliding window)
CONFIG_SWA = CONFIG.with_(sliding_window=4096)
