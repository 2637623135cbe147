"""deepseek-7b — llama-architecture dense decoder.  [arXiv:2401.02954; hf]

30L d_model=4096 32H (kv=32, full multi-head attention) d_ff=11008
vocab=102400, rope theta 1e4, rms_norm eps 1e-6.
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-7b", family="dense",
    n_layers=30, d_model=4096, n_heads=32, n_kv_heads=32,
    d_ff=11008, vocab_size=102400, rope_theta=10000.0, norm_eps=1e-6,
)
