"""h2o-danube-1.8b — a Mistral-type decoder with sliding-window attention.

As published (H2O-Danube-1.8B, arXiv:2401.16818; the model's
``config.json`` at
https://huggingface.co/h2oai/h2o-danube-1.8b-base/blob/main/config.json):
24 layers, d_model 2560, 32 query heads over 8 kv heads (GQA 4:1),
head_dim 80, SwiGLU MLP of width 6912, RMSNorm with eps 1e-5, RoPE with
theta 10000, sliding-window attention over 4096 positions, 16384
positions in all, vocabulary 32000 with an untied head, bfloat16.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="h2o-danube-1.8b",
    family="dense",
    num_layers=24,
    d_model=2560,
    num_heads=32,
    num_kv_heads=8,
    d_ff=6912,
    vocab_size=32000,
    activation="swiglu",
    norm="rmsnorm",
    norm_eps=1e-5,
    rope_theta=1e4,
    tie_embeddings=False,
    attention_kind="swa",
    window=4096,
    param_dtype="bfloat16",
    max_seq_len=16384,
)
