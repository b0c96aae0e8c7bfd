"""The raw median time to first token, from the due time, over the requests
due inside the window. With some tens of requests whose prompts differ
fourteenfold it hangs on which prompts the window held, so it carries no
bound; ``ttft_per_256tok_p50_ms`` is the end-to-end metric it explains."""
from benchmark import reduce


def read(run):
    first = reduce.ttfts(run.recs, run.t_open, run.t_end)
    return 1e3 * reduce.percentile(first, 0.5) if first else None
