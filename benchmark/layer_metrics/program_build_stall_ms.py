"""Engine: loop time that went into building programs on first use inside the
window (tracing, lowering, compiling or loading from the persistent cache, as
``jax.monitoring`` timed each on the loop thread): the time behind
``program_loads_in_window`` and ``compiles_in_window``. Every slot waits while
it lasts. Program counters (the phase clock's ``program_build``)."""

from benchmark.layer_metrics._loop import phase_s


def read(run):
    seconds = phase_s(run, run["counters"], "program_build")
    return None if seconds is None else seconds * 1e3
