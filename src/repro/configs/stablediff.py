"""StableDiff U-Net configs — the paper's own targets (Sec. VI-A).

* sd_v14 / sd_v21: latent 64x64 (512x512 images), 860M-class U-Net.
* sd_xl: the SDXL base 1.0 U-Net (stabilityai/stable-diffusion-xl-base-1.0,
  ``unet/config.json``): latent 128x128 (1024x1024 images), 3 levels
  320/640/1280 with attention at levels 1-2, heads 10 and 20 of 64, one
  Transformer2D per site of 2 and 10 BasicTransformerBlocks (mid: 10),
  2048-wide conditioning, and the ``text_time`` added conditioning (a
  1280-wide pooled embedding and the six size/crop ids of a 1024x1024
  generation).
* TOY: a trainable-on-CPU latent-diffusion model with the same topology,
  used by the end-to-end example and the PAS quality experiments.
"""
from repro.common.types import DiffusionConfig, UNetConfig

SD_V14 = UNetConfig(
    name="sd_v14",
    base_channels=320,
    channel_mult=(1, 2, 4, 4),
    n_res_blocks=2,
    attn_levels=(0, 1, 2),
    n_heads=8,
    tf_depth=1,
    ctx_dim=768,
    ctx_len=77,
    time_dim=1280,
    latent_size=64,
    dtype="bfloat16",
)

SD_V21 = UNetConfig(
    name="sd_v21",
    base_channels=320,
    channel_mult=(1, 2, 4, 4),
    n_res_blocks=2,
    attn_levels=(0, 1, 2),
    n_heads=10,  # v2.x uses head_dim 64 per level; approximated globally
    tf_depth=1,
    ctx_dim=1024,
    ctx_len=77,
    time_dim=1280,
    latent_size=64,
    dtype="bfloat16",
)

SD_XL = UNetConfig(
    name="sd_xl",
    base_channels=320,
    channel_mult=(1, 2, 4),
    n_res_blocks=2,
    attn_levels=(1, 2),
    level_heads=(5, 10, 20),
    level_depths=(1, 2, 10),
    ctx_dim=2048,
    ctx_len=77,
    time_dim=1280,
    latent_size=128,
    dtype="bfloat16",
    pooled_dim=1280,
    time_ids=(1024, 1024, 0, 0, 1024, 1024),  # original size, crop, target size
)

# ~100M-parameter member of the family for the end-to-end training example
SD_100M = UNetConfig(
    name="sd_100m",
    base_channels=128,
    channel_mult=(1, 2, 4),
    n_res_blocks=2,
    attn_levels=(0, 1, 2),
    n_heads=4,
    tf_depth=1,
    ctx_dim=128,
    ctx_len=16,
    time_dim=512,
    latent_size=32,
    dtype="float32",
)

TOY = UNetConfig(
    name="sd_toy",
    base_channels=32,
    channel_mult=(1, 2, 4),
    n_res_blocks=1,
    attn_levels=(0, 1),
    n_heads=2,
    tf_depth=1,
    ctx_dim=32,
    ctx_len=8,
    time_dim=128,
    groups=8,
    latent_size=16,
    dtype="float32",
)

DIFFUSION_50 = DiffusionConfig(timesteps_sample=50, scheduler="pndm", guidance_scale=7.5)
DIFFUSION_TOY = DiffusionConfig(timesteps_sample=25, scheduler="pndm", guidance_scale=3.0)
