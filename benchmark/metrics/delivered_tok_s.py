"""Output tokens per second counted by whole deliveries: every token line
that reached a client inside the window, over the window's seconds."""
from benchmark import reduce

NAMES = ("delivered_tok_s", "delivered_tok_s.closed")


def read(ctx):
    return reduce.tokens_delivered_whole(ctx["records"], ctx["seconds"]) \
        / ctx["seconds"]
