"""Client mean time to first token less the server's own (submit to the
prefill's pick): what HTTP, the handler threads and the first emission's
wait for a window to end add."""
from benchmark import reduce
from benchmark.metrics import hist_mean

NAMES = ("http_over_ms", "http_over_ms.closed")


def read(ctx):
    server = hist_mean(ctx, "ttft_ms")
    if server is None or not ctx["window"]:
        return None
    client = sum(reduce.ttft_ms(r) for r in ctx["window"])
    return client / len(ctx["window"]) - server
