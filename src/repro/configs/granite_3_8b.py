"""granite-3-8b [dense]: 40L d=4096 32H (GQA kv=8) d_ff=12800 vocab=49155.
Source: hf:ibm-granite family (GQA, SwiGLU, RoPE)."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="granite-3-8b", family="dense",
    n_layers=40, d_model=4096, n_heads=32, n_kv=8, d_ff=12800, vocab=49155,
    mlp="swiglu", accum=2,
)

def reduced() -> ModelConfig:
    return CONFIG.replace(n_layers=2, d_model=64, n_heads=4, n_kv=2, d_ff=128,
                          vocab=512, accum=1, attn_chunk=64)


def one_chip() -> ModelConfig:
    """Published widths, cut in depth to 20 of the 40 layers: what one TPU
    v5e chip (16 GiB of HBM) holds with room to serve.

    By ``ModelConfig.n_params`` the whole model is 8.37e9 parameters, 15.6
    GiB in bf16, which does not fit.  At 20 layers it is 4.39e9, 8.2 GiB.
    KV costs 4 KiB per token per layer (2 x 8 KV heads x 128 x bf16), so 8
    slots x 1024 positions x 20 layers is 0.63 GiB; serving donates no
    buffers, so a decode step holds it twice.  The remaining layers would sit
    on further chips as pipeline stages."""
    return CONFIG.replace(n_layers=20)
