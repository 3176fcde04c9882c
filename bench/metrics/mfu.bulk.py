"""Useful model FLOPs over the traced window (its length on the host
clock, as ``images_per_s`` takes it) at the chip's int8 peak, in
percent: real images classified (not padding rows) times the forward's
FLOPs per image, from the configuration's shapes."""
from bench.harness.manifest import work


def read(run):
    if run.trace is None:
        return None
    cfg = run.cell.config
    images = sum(n for _, _, n in run.steps)
    flops = images * work(cfg["family"]).flops(cfg, 1)
    return 100.0 * flops / run.window_s / run.peaks["int8_ops_per_s"]
