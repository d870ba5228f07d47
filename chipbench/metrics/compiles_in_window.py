"""XLA programs compiled during the window, less those loaded from the
persistent cache (JAX's monitoring events). Each publish that changes the
probe's static table descriptors costs one on the next read."""


def read(run):
    return run.compiles_in_window
