"""Seconds from the start of the benchmark's process to its first timed
replay: JAX and the chip, traces and fleet, the U-Net's programs."""


def read(run):
    return run.setup_s
