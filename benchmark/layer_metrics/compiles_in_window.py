"""Engine: programs XLA compiled inside the window (jax.monitoring backend
compile events less persistent-cache hits). Has to read 0: warm-up missed a
shape if not."""


def read(run):
    return run["compiles"]["compiles"]
