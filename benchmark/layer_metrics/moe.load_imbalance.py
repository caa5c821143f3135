"""Expert layer: the most loaded held expert's pairs over the mean held
expert's, over the window (deltas of ``moe.expert_pairs.<i>``, summed
over the sparse layers inside the programs). 1.0 is an even load; the
grouped matmul's time follows the largest group."""
import re


def read(ctx):
    rx = re.compile(r"^moe\.expert_pairs\.\d+$")
    loads = [v for k, v in ctx["counters"].items() if rx.search(k)]
    if not loads or not sum(loads):
        return None
    return max(loads) / (sum(loads) / len(loads))
