"""setup_s: seconds from the start of the benchmark's process to the
first timed loop: JAX and the chip coming up, the inputs made on the
device, and one whole warm-up loop (compiles, or loads from the
persistent cache, every program the window runs)."""


def read(ctx):
    return ctx.setup_s
