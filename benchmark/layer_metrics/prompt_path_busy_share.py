"""Model step: the prompt path's share of the device's busy time in the traced
span: module seconds of the chunk-prefill and finalize programs (the roles of
``programs.json``) over the union of the first device's operation intervals. The
rest is the decode program. A run without a trace gives nothing."""


def read(run):
    trace = run["trace"]
    if not trace or trace["first_device"]["busy_s"] <= 0:
        return None
    programs = trace["programs"]
    if not programs.get("chunk_prefill", {}).get("events"):
        return None
    prompt = programs["chunk_prefill"]["seconds"] + programs.get("finalize", {}).get("seconds", 0.0)
    return 100.0 * prompt / trace["first_device"]["busy_s"]
