"""The busiest held expert's picks over the held experts' mean, over
the window (``expert_picks_by_expert``, summed over layers): 1 under
even routing; the product over all held experts costs the same
whatever it reads, a grouped one would pay the busiest."""
NAMES = ("expert_imbalance.closed",)


def read(ctx):
    a = ctx["stats_start"].get("expert_picks_by_expert")
    b = ctx["stats_end"].get("expert_picks_by_expert")
    if not a or not b or len(a) != len(b):
        return None
    grown = [after - before for before, after in zip(a, b)]
    total = sum(grown)
    return max(grown) * len(grown) / total if total > 0 else None
