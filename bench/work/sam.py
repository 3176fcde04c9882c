"""Operations and bytes of a Segment Anything image encoder forward, from
the configuration's shapes.

These count the work any implementation of the model must do, not what
this program's kernels happen to do: FLOPs are 2*M*K*N per matrix
product at the model's shapes before any padding of rows, columns or
head dims; q/k/v and the output projection run over the real tokens
only (the padded tokens of a window layer have the biases as q/k/v, so
no implementation must project them), and window attention runs over
the windows of the padded grid, every window's keys padded ones
included, as the published model attends.  Bytes are every operand and
result at its MXInt width (mantissa bits plus one 8-bit exponent per
block).
"""
from __future__ import annotations


def grid(cfg: dict) -> int:
    return cfg["image_size"] // cfg["patch_size"]


def windows(cfg: dict) -> int:
    """Windows of a window layer: the grid padded to a multiple of the
    window, cut into window x window squares."""
    return (-(-grid(cfg) // cfg["window_size"])) ** 2


def layers(cfg: dict) -> list:
    """Per layer, ``'global'`` or ``'window'``."""
    return ["global" if i in cfg["global_attn_indexes"] else "window"
            for i in range(cfg["num_hidden_layers"])]


def linears(cfg: dict, images: int) -> list:
    """(name, M, K, N) of every linear of one forward over ``images``: the
    patch embedding, each block's q, k, v, out, wi, wo, and the neck's
    two convs (1x1: d -> c; 3x3 over each pixel's 3x3xc neighbourhood)."""
    d, ff, c = cfg["hidden_size"], cfg["intermediate_size"], cfg["out_chans"]
    rows = images * grid(cfg) ** 2
    block = [("q", rows, d, d), ("k", rows, d, d), ("v", rows, d, d),
             ("out", rows, d, d), ("wi", rows, d, ff), ("wo", rows, ff, d)]
    return ([("patch", rows, 3 * cfg["patch_size"] ** 2, d)]
            + block * cfg["num_hidden_layers"]
            + [("neck1", rows, d, c), ("neck2", rows, 9 * c, c)])


def _attention_flops(seqs: int, side: int, d: int) -> float:
    """Scores and p @ V (2 * 2 * S^2 * d) plus the two relative-position
    terms (2 * 2 * S * side * d) of ``seqs`` sequences of side^2 tokens."""
    s = side * side
    return seqs * (4.0 * s * s * d + 4.0 * s * side * d)


def global_attention_flops(cfg: dict, images: int) -> float:
    """Every global layer's attention over ``images``."""
    n = layers(cfg).count("global")
    return n * _attention_flops(images, grid(cfg), cfg["hidden_size"])


def attention_flops(cfg: dict, images: int) -> float:
    """Every layer's attention over ``images``: global layers over the
    whole grid, window layers over their windows."""
    n = layers(cfg).count("window")
    return (global_attention_flops(cfg, images)
            + n * _attention_flops(images * windows(cfg), cfg["window_size"],
                                   cfg["hidden_size"]))


def flops(cfg: dict, images: int) -> float:
    """Matrix-product FLOPs of one forward over ``images``."""
    return (sum(2 * m * k * n for _, m, k, n in linears(cfg, images))
            + attention_flops(cfg, images))


def _bits(mant_bits: int, block: int) -> float:
    return mant_bits + 8.0 / block


def linear_bytes(cfg: dict, m: int, k: int, n: int) -> float:
    """Activations in and out at the activation format, weights at the
    weight format; blocks run along K (clamped to K as the datapath
    clamps them)."""
    dp = cfg["datapath"]
    act = lambda dim: _bits(dp["act_mant_bits"], min(dp["act_block"], dim))
    w = _bits(dp["weight_mant_bits"], min(dp["weight_block"], k))
    return (m * k * act(k) + k * n * w + m * n * act(n)) / 8.0


def linear_least_s(cfg: dict, images: int, peaks: dict) -> float:
    """Least time of the forward's linears on a chip with ``peaks``: for
    each, the larger of its operations at the int8 peak and its bytes at
    the HBM bandwidth."""
    return sum(max(2 * m * k * n / peaks["int8_ops_per_s"],
                   linear_bytes(cfg, m, k, n) / peaks["hbm_bytes_per_s"])
               for _, m, k, n in linears(cfg, images))


def global_attention_least_s(cfg: dict, images: int, peaks: dict) -> float:
    """Least time of the global layers' attention: the larger of its
    FLOPs at the bf16 peak (the scores are float products of blocks with
    different exponents, which the int8 peak does not bound) and q, k, v
    and the output at MXInt8 width at the HBM bandwidth."""
    dp = cfg["datapath"]
    n = layers(cfg).count("global")
    tokens = images * grid(cfg) ** 2
    moved = n * 4 * tokens * cfg["hidden_size"] * _bits(
        dp["act_mant_bits"], dp["act_block"]) / 8.0
    return max(global_attention_flops(cfg, images) / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
