"""Model step (models/llama.py through the engine's decode program): device
time of the decode program's events in the trace, per decode step (a dispatch
runs `chunk` steps)."""


def read(run):
    decode = (run["trace"] or {}).get("programs", {}).get("decode")
    if not decode or not decode["events"]:
        return None
    return decode["seconds"] * 1e3 / (decode["events"] * run["serve"]["chunk"])
