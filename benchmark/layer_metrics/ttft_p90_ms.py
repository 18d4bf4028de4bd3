"""Engine (queueing, chunk-boundary admission, prefill cutting into decode), on
the client's clock: 90th percentile over the requests due in the window of
first content event - due time. Recorded without a bound: at some 60 requests a window it spreads by
more than any bound may allow (PERF.md, section 2)."""
from benchmark.harness.records import end_to_end


def read(run):
    return end_to_end(run["records"], *run["window"])["ttft_p90_ms"]
