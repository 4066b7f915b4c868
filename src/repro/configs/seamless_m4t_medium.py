"""SeamlessM4T-medium [arXiv:2308.11596] — enc-dec multimodal (audio).

The speech frontend (mel filterbank + conv feature extractor) is the stubbed
modality frontend: the batch carries precomputed frame embeddings of shape
(B, T_src, d_model). We build the 12L transformer encoder + 12L decoder with
cross-attention over the 256206-entry text vocabulary.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="seamless-m4t-medium", family="encdec",
    n_layers=12, n_encoder_layers=12, d_model=1024, n_heads=16, n_kv_heads=16,
    d_ff=4096, vocab_size=256206, rope_theta=1e4,
    source="arXiv:2308.11596",
)
