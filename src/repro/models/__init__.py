from repro.models.model_api import (ModelConfig, MoEConfig, ShapeConfig,
                                    Param, unwrap, axes_tree, is_param,
                                    TRAIN_4K, PREFILL_32K, DECODE_32K,
                                    LONG_500K, ALL_SHAPES, shape_by_name)
from repro.models.transformer import DecoderLM, EncDecLM
from repro.models.sam import SamViT
from repro.models.vit import ViT


def build_model(cfg: ModelConfig):
    if cfg.family == "vit":
        return ViT(cfg)
    if cfg.family == "sam":
        return SamViT(cfg)
    if cfg.is_encoder_decoder:
        return EncDecLM(cfg)
    return DecoderLM(cfg)
