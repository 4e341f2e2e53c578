"""internvl3-14b — the paper's own primary evaluation model (Table 2):
InternViT-300M + Qwen2.5-14B backbone.  Not part of the assigned pool;
included so the paper's experimental configuration is representable.

``CONFIG_1CHIP`` is the share of it that one TPU v5e chip (16 GB HBM)
serves through the normal path (``Scheduler`` -> ``ServingPipeline`` ->
paged KV -> Pallas kernels):

  * source: the full config below (arXiv:2504.10479, Table 2; LM widths
    of Qwen2.5-14B, vision tower InternViT-300M at 448 px);
  * deployment it stands for: the 48 LM layers split into 3 pipeline
    stages of 16, one per chip; this chip holds the first stage
    together with the vision tower, the embedding and the LM head;
  * cut: depth only (``REDUCED_1CHIP``).  Every width is published:
    d_model 5120, 40 query / 8 KV heads of 128, d_ff 13824, the whole
    vocabulary, and the whole 24-layer ViT (d 1024, 16 heads, 448-px
    frames, 256 tokens per frame after 2x2 grouping);
  * why this depth: v5e ``memory_analysis()`` of the serving programs
    at 2 streams, window 16, stride 4.  Weights (bf16) take 3.11 GB for
    the untied embedding and head, 0.85 GB for the ViT and projector,
    and 0.55 GB per LM layer: 12.77 GB at 16 layers.  The largest step
    is the fullcomp full-frame ViT encode (32 frames, 2.28 GB of
    temporaries); the prefill steps add the KV (0.35 GB paged slab,
    0.55 GB dense caches) and under 0.45 GB of temporaries.  16 layers
    put the estimated peak near 15.1 GB of the 16.9 GB the v5e runtime
    offers; 20 layers would not fit.  On a v5e, ``chip_smoke.py`` (both
    modes and its oracle check) peaked at 15.60 GB, 7.7% free.
"""
import dataclasses

from .base import ModelCfg, ViTCfg

CONFIG = ModelCfg(
    name="internvl3-14b",
    family="vlm",
    n_layers=48,
    d_model=5120,
    n_heads=40,
    n_kv=8,
    d_ff=13824,
    vocab=151674,
    img_tokens=256,
    vit=ViTCfg(n_layers=24, d_model=1024, n_heads=16, d_ff=4096,
               patch=14, image=448, group=2),
    source="arXiv:2504.10479 (paper Table 2)",
)

N_LAYERS_1CHIP = 16

CONFIG_1CHIP = dataclasses.replace(
    CONFIG,
    name="internvl3-14b-1chip",
    n_layers=N_LAYERS_1CHIP,
    source="arXiv:2504.10479 (paper Table 2), depth cut to one v5e chip",
)

# keys changed from the source, with the published value
REDUCED_1CHIP = {"n_layers": f"{CONFIG.n_layers} -> {N_LAYERS_1CHIP}"}
