"""Engine: programs first used inside the window and loaded from the persistent
compile cache: shapes the engine builds lazily (prefix-store slices, page
scatters) that no warm-up reaches. Each stalls the engine's loop for a load."""


def read(run):
    return run["compiles"]["cache_loads"]
