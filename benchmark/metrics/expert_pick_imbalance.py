"""``expert_imbalance.closed``'s reading (the busiest held expert's
picks over the held experts' mean, from ``expert_picks_by_expert``; 1
under even routing) in a cell that the accepted metric's ``workloads``
does not list, under a name of its own: an entry the benchmark has is a
``benchmark`` PR's to edit, and appending the cell there makes this file
and its entry redundant. With every expert held it is the imbalance of
the whole layer's routing: what a grouped product would pay over the
product that reads all experts whatever the routing."""
from benchmark.metrics import expert_imbalance

NAMES = ("expert_pick_imbalance.closed",)
read = expert_imbalance.read
