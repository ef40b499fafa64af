"""Set-up: process start to the window's start (JAX and the chip, the
weights, the engine and server, the warm-up requests)."""


def read(run):
    return run.setup_s
