"""ViT / DeiT — the paper's own model family (§IV: DeiT Tiny/Small/Base).

Patchify is an exact reshape + linear (equivalent to the stride-16 conv),
class token + learned position embeddings, pre-LayerNorm encoder blocks with
GELU MLPs, classification head on the CLS token — the standard DeiT
architecture the paper quantizes.

Every operator routes through the quantization-aware layer primitives, so a
`QuantConfig(mode='sim', quantize_nonlinear=True)` config runs the FULL
bit-accurate MXInt datapath end-to-end: MXInt linears, Fig-3 LayerNorm,
Eq-12 GELU and Eq-14..20 Softmax — the configuration of the paper's final
accelerator.  `mode='kernel'` runs the same datapath through the Pallas
kernels: packed int8 weight planes into `mxint_linear`, the non-linear ops
and the attention softmax in-kernel — bit-identical to 'sim' (enforced by
tests/test_kernel_mode.py) and the deployment path of
`serving.ViTServingEngine`.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.mx_types import QuantConfig
from repro.models import layers as L
from repro.models import attention as A
from repro.models.model_api import (ModelConfig, Param, dense_init,
                                    ones_init, zeros_init, is_param)
from repro.models.transformer import _stacked_init


class ViT:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        assert cfg.image_size % cfg.patch_size == 0
        self.n_patches = (cfg.image_size // cfg.patch_size) ** 2
        self.seq = self.n_patches + 1                     # + CLS
        self.out_shape = (cfg.n_classes,)                 # per image

    # -- params -------------------------------------------------------------
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        d = cfg.d_model
        dtype = cfg.dtype
        patch_dim = cfg.patch_size * cfg.patch_size * 3
        ks = jax.random.split(rng, 6)
        params = {
            "patch_proj": dense_init(ks[0], (patch_dim, d),
                                     ("patch", "embed"), dtype=dtype),
            "patch_bias": zeros_init((d,), ("embed",), dtype=dtype),
            "cls_token": dense_init(ks[1], (1, 1, d), (None, None, "embed"),
                                    scale=0.02, dtype=dtype),
            "pos_embed": dense_init(ks[2], (self.seq, d), (None, "embed"),
                                    scale=0.02, dtype=dtype),
            "blocks": _stacked_init(lambda k: self._init_block(k, dtype),
                                    ks[3], cfg.n_layers),
            "final_ln_g": ones_init((d,), ("embed",), dtype=dtype),
            "final_ln_b": zeros_init((d,), ("embed",), dtype=dtype),
            "head": dense_init(ks[4], (d, cfg.n_classes),
                               ("embed", "classes"), dtype=dtype),
            "head_b": zeros_init((cfg.n_classes,), ("classes",), dtype=dtype),
        }
        return params

    def _init_block(self, key, dtype):
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        return {
            "ln1_g": ones_init((cfg.d_model,), ("embed",), dtype=dtype),
            "ln1_b": zeros_init((cfg.d_model,), ("embed",), dtype=dtype),
            "attn": A.init_attn_params(ks[0], cfg, dtype),
            "ln2_g": ones_init((cfg.d_model,), ("embed",), dtype=dtype),
            "ln2_b": zeros_init((cfg.d_model,), ("embed",), dtype=dtype),
            "ffn": {
                "wi": dense_init(ks[1], (cfg.d_model, cfg.d_ff),
                                 ("embed", "mlp"), dtype=dtype),
                "bi": zeros_init((cfg.d_ff,), ("mlp",), dtype=dtype),
                "wo": dense_init(ks[2], (cfg.d_ff, cfg.d_model),
                                 ("mlp", "embed"), dtype=dtype),
                "bo": zeros_init((cfg.d_model,), ("embed",), dtype=dtype),
            },
        }

    # -- forward --------------------------------------------------------------
    def patchify(self, images: jnp.ndarray) -> jnp.ndarray:
        """(b, H, W, 3) -> (b, n_patches, patch_dim); exact stride-P conv.

        Channel-major feature layout (c slowest) so per-block shared
        exponents align with channels — microscaling then isolates
        outlier channels into their own blocks (paper Fig. 1a rationale).
        """
        cfg = self.cfg
        b, h, w, c = images.shape
        p = cfg.patch_size
        x = images.reshape(b, h // p, p, w // p, p, c)
        x = x.transpose(0, 1, 3, 5, 2, 4)
        return x.reshape(b, (h // p) * (w // p), c * p * p)

    def features(self, params, images):
        cfg = self.cfg
        quant = cfg.quant
        x = self.patchify(images.astype(cfg.dtype))
        x = L.linear(x, params["patch_proj"], params["patch_bias"], q=quant,
                     scope="patch")
        cls = jnp.broadcast_to(params["cls_token"].value.astype(x.dtype),
                               (x.shape[0], 1, x.shape[-1]))
        x = jnp.concatenate([cls, x], axis=1)
        x = x + params["pos_embed"].value.astype(x.dtype)[None]

        def block(x, bp, attn_scope=None, ffn_scope=None):
            # pre-norms ride into the consuming linears through the
            # layernorm_linear composite seam: fused LN->qkv / LN->wi in
            # kernel mode, norm-then-linear otherwise (DESIGN.md §12)
            o, _ = A.attention(bp["attn"], x, cfg, quant=quant,
                               positions=jnp.arange(x.shape[1])[None, :],
                               causal=False, use_rope=False,
                               prenorm=("ln", bp["ln1_g"], bp["ln1_b"]),
                               scope=attn_scope)
            x = x + o
            return x + L.ffn(x, bp["ffn"], "gelu", quant,
                             prenorm=("ln", bp["ln2_g"], bp["ln2_b"]),
                             eps=cfg.norm_eps, scope=ffn_scope)

        remat = cfg.remat in ("block", "full")
        if quant.has_overrides:
            # per-layer-group overrides are STATIC per block (different
            # formats/backends per layer), which one scanned trace cannot
            # carry — unroll over blocks, slicing each layer's params out
            # of the stacked tree (DESIGN.md §16)
            for i in range(cfg.n_layers):
                bp = jax.tree_util.tree_map(lambda a, i=i: a[i],
                                            params["blocks"])
                step = (lambda x, bp=bp, i=i:
                        block(x, bp, f"block/{i}/attn", f"block/{i}/ffn"))
                x = jax.checkpoint(step)(x) if remat else step(x)
        else:
            def scan_block(x, bp):
                return block(x, bp), None
            if remat:
                scan_block = jax.checkpoint(scan_block)
            x, _ = jax.lax.scan(scan_block, x, params["blocks"])
        return L.layernorm(x, params["final_ln_g"], params["final_ln_b"],
                           q=quant, eps=cfg.norm_eps, scope="final_ln")

    def logits(self, params, images):
        x = self.features(params, images)
        pooled = x[:, 0] if self.cfg.pool == "cls" else x.mean(1)
        return L.linear(pooled, params["head"], params["head_b"],
                        q=self.cfg.quant, scope="head")

    def loss(self, params, batch):
        """batch: {'images': (b,H,W,3), 'labels': (b,) int32}."""
        logits = self.logits(params, batch["images"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, batch["labels"][:, None], -1)[:, 0]
        return jnp.mean(nll)

    def accuracy(self, params, batch):
        logits = self.logits(params, batch["images"])
        return jnp.mean((jnp.argmax(logits, -1) == batch["labels"])
                        .astype(jnp.float32))
