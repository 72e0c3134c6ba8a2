"""Share of the ENTRY device time of the traced window that the program's
scopes do NOT place, in %: events the scope map has no instruction for (the
join failed: the witness that the map is of the executable that ran) and
events whose scope path is empty (under no block, ``mx_loss``,
``mx_update`` or other ``mx_*`` scope), over all ENTRY device time.
``args["kinds"]`` names the rows of ``scope_ms_per_step``'s table that count
as not placed.  Nothing where the program hands out no map."""
import os

from benchmark import loader

HERE = os.path.dirname(os.path.abspath(__file__))


def read(obs, args):
    scope = loader.load_module(os.path.join(HERE, "scope_ms_per_step.py"),
                               "benchmark_metric_scope_ms_per_step")
    tab = scope.cell_table(obs)
    if tab is None or not tab["entry_s"]:
        return None
    lost = sum(sum(tab.get(kind, {}).values()) for kind in args["kinds"])
    return 100.0 * lost / tab["entry_s"]
