"""From process start to the window's start: JAX import, weights made on
the device from the seed, program loads or compiles, and warm-up."""


def read(run):
    return run.setup_s
