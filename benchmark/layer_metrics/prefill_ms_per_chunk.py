"""Model step (models/llama.py through the engine's chunk-prefill program): device
time of the chunk-prefill programs' events in the traced span, per program: one
chunk of a prompt (at most the engine's chunk cap of tokens, 512 for
``laguna-s-ep2``) through every layer, over the row as far as it is filled. In
the ``laguna-s-ep2.repo-context`` cell these programs are most of the busy chip
(the softmax of the full layers' scores over a long row, the held experts read
once a chunk), so this is the number a change to the prompt path moves first. A
run without a trace, or a span that holds no chunk prefill, gives nothing."""


def read(run):
    chunks = (run["trace"] or {}).get("programs", {}).get("chunk_prefill")
    if not chunks or not chunks["events"]:
        return None
    return chunks["seconds"] * 1e3 / chunks["events"]
