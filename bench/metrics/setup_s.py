"""Process start to the first timed step: imports, device start-up,
seeded weights on the device, packing, and warm-up of the cell's one
batch shape."""


def read(run):
    return run.setup_s
