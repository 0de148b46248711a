"""Of the held experts' matrices the decode steps read, the share that
got one or more picks of a live row: 100 x the growth of
``expert_touched_total`` ((layer, held expert, step) triples with a
pick) over the growth of ``decode_steps_total`` times
``expert_reads_per_step`` (layers x held experts). 80 under even routing
where 64 rows pick 8 of 320; the rest is what a product that skipped the
untouched experts would not have to read."""
from benchmark.metrics import _counters

NAMES = ("expert_touched_pct.closed",)


def read(ctx):
    steps = _counters.delta(ctx, "decode_steps_total")
    per_step = ctx["stats_end"].get("expert_reads_per_step")
    if steps is None or per_step is None:
        return None
    return _counters.ratio(_counters.delta(ctx, "expert_touched_total"),
                           steps * per_step, 100.0)
