"""Operations and bytes of a ViT forward, from the configuration's shapes.

These count the work any implementation of the model must do, not what
this program's kernels happen to do: FLOPs are 2*M*K*N per matrix
product at the model's shapes (before any padding of rows or columns),
and bytes are every operand and result of a linear at its MXInt width
(mantissa bits plus one 8-bit exponent per block).
"""
from __future__ import annotations


def tokens(cfg: dict) -> int:
    return (cfg["image_size"] // cfg["patch_size"]) ** 2 + 1


def linears(cfg: dict, images: int) -> list:
    """(name, M, K, N) of every linear of one forward over ``images``."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    t = tokens(cfg)
    rows = images * t
    block = [("q", rows, d, d), ("k", rows, d, d), ("v", rows, d, d),
             ("out", rows, d, d), ("wi", rows, d, ff), ("wo", rows, ff, d)]
    return ([("patch", images * (t - 1), 3 * cfg["patch_size"] ** 2, d)]
            + block * cfg["num_hidden_layers"]
            + [("head", images, d, cfg["num_labels"])])


def attention_flops(cfg: dict, images: int) -> float:
    """Scores and probability-weighted values of every layer."""
    t = tokens(cfg)
    return 2 * 2 * images * t * t * cfg["hidden_size"] * cfg["num_hidden_layers"]


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
