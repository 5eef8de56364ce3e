"""rwkv6-1.6b [ssm]: Finch 1.6B, the rwkv6-3b layer at width 2048.
[arXiv:2404.05892; hf RWKV/v6-Finch-1B6-HF]"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="rwkv6-1.6b", family="ssm",
    n_layers=24, d_model=2048, n_heads=32, n_kv_heads=32, d_ff=7168,
    vocab=65536, ssm_head_dim=64, subquadratic=True, tie_embeddings=False,
    notes="Attention-free; n_heads is derived (2048/64).",
)
