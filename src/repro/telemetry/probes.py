"""Runnable kernel probes keyed by static cost-model row label
(DESIGN.md §15).

Each probe executes the SAME kernel configuration the analysis sweep
captures under that label (``repro.analysis.kernel_contracts``) — but
for real, on concrete arrays, with the wall-clock recorded as a
``span/kernel:<label>/ms`` histogram.  That shared label is the join
key :func:`repro.telemetry.export.predicted_vs_measured` uses, so a
probe drifting from its sweep twin shows up as an ``unmatched`` row in
the report rather than a silently wrong join.

This is the one telemetry module that imports the kernel stack — and
only inside the probe bodies, keeping ``metrics``/``tracing``/``export``
importable without jax.  On CPU the kernels run in Pallas interpret
mode (the ``ops._interpret()`` gate), so probe wall-clocks there
measure the interpreter, not the datapath — the predicted-vs-measured
fractions only mean something on compiled hardware, but the plumbing
(spans, join, report) is identical.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

from repro.telemetry import metrics
from repro.telemetry.tracing import span


def _rng(seed: int = 0):
    import numpy as np
    return np.random.default_rng(seed)


def _probe_matmul_deit() -> Callable[[], object]:
    """DeiT-Tiny model-path linear: 2x197 tokens padded to 400 rows,
    d=192, OCP-32 weight blocks, lanes padded to 256 — the shape
    ``ops.mxint_linear`` launches for the qkv/proj/FFN projections
    (sweep twin: ``matmul-deit``)."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.mxint_matmul import mxint_matmul

    rng = _rng(0)
    x = jnp.asarray(rng.normal(size=(400, 192)), jnp.float32)
    mant = jnp.asarray(rng.integers(-127, 128, (192, 256)), jnp.int8)
    exp = jnp.asarray(rng.integers(-8, 2, (6, 256)), jnp.int8)
    interp = ops._interpret()
    return lambda: mxint_matmul(
        x, mant, exp, w_block=32, act_block=16, act_mant_bits=8,
        quantize_act=True, bm=ops.linear_row_tile(394, 192), bn=128,
        interpret=interp, out_dtype=jnp.float32)


def _probe_flash_deit() -> Callable[[], object]:
    """DeiT padded attention: (b*h=6, 197->200, 64->128), kv padded to
    256 with the kv_len mask (sweep twin: ``flash-deit``)."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.flash_attention import flash_attention

    rng = _rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(6, s, 128)) * 0.1,
                           jnp.float32) for s in (200, 256, 256))
    interp = ops._interpret()
    return lambda: flash_attention(
        q, k, v, causal=False, block_q=8, block_k=128, kv_len=197,
        interpret=interp)


def _probe_matmul_bench() -> Callable[[], object]:
    """kernel_bench matmul shape: 128x1024 @ 1024x512, paper W-block 256
    (sweep twin: ``matmul-bench``)."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.mxint_matmul import mxint_matmul

    rng = _rng(2)
    x = jnp.asarray(rng.normal(size=(128, 1024)), jnp.float32)
    mant = jnp.asarray(rng.integers(-127, 128, (1024, 512)), jnp.int8)
    exp = jnp.asarray(rng.integers(-8, 2, (4, 512)), jnp.int8)
    interp = ops._interpret()
    return lambda: mxint_matmul(
        x, mant, exp, w_block=256, act_block=16, act_mant_bits=8,
        quantize_act=True, bm=128, bn=128, interpret=interp,
        out_dtype=jnp.float32)


def _probe_ln_matmul_bench() -> Callable[[], object]:
    """Fused LN->linear bench shape: 256x768 @ 768x768, OCP-32 (sweep
    twin: ``ln-matmul-bench``)."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.mxint_ln_matmul import mxint_ln_matmul

    rng = _rng(3)
    x = jnp.asarray(rng.normal(size=(256, 768)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(768,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(768,)), jnp.float32)
    mant = jnp.asarray(rng.integers(-127, 128, (768, 768)), jnp.int8)
    exp = jnp.asarray(rng.integers(-8, 2, (24, 768)), jnp.int8)
    interp = ops._interpret()
    return lambda: mxint_ln_matmul(
        x, g, b, mant, exp, w_block=32, act_block=16, mant_bits=8,
        lut_bits=5, bm=128, bn=128, interpret=interp)


PROBES: Dict[str, Callable[[], Callable[[], object]]] = {
    "matmul-deit": _probe_matmul_deit,
    "flash-deit": _probe_flash_deit,
    "matmul-bench": _probe_matmul_bench,
    "ln-matmul-bench": _probe_ln_matmul_bench,
}

# the default pair: the paper's DeiT deployment kernels (matmul + flash
# attention), the acceptance join of ISSUE 9
DEFAULT_PROBES: Tuple[str, ...] = ("matmul-deit", "flash-deit")


def run_probes(labels: Sequence[str] = DEFAULT_PROBES, repeats: int = 2,
               registry: Optional[metrics.Registry] = None) -> dict:
    """Build, warm (compile), then time each probe ``repeats`` times
    under a ``kernel:<label>`` span.  Returns ``{label: mean_ms}``."""
    import jax

    out = {}
    for label in labels:
        fn = PROBES[label]()
        jax.block_until_ready(fn())          # compile / first-call cost
        for _ in range(repeats):
            with span(f"kernel:{label}", registry=registry):
                jax.block_until_ready(fn())
        reg = registry or metrics.default_registry()
        out[label] = reg.histogram(f"span/kernel:{label}/ms").mean
    return out
