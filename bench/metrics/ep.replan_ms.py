"""Host milliseconds of one expert re-placement inside the window, the
median over the window's replans: ``rebalance_experts`` (the CCM-LB plan
on the routed counts and the permutation of the experts, the router and
the AdamW moments on the chips), ended by ``block_until_ready``.  ``None``
where the window replanned nothing."""
import statistics


def read(run, trace, peaks):
    times = run.counters.get("replan_s")
    if not times:
        return None
    return statistics.median(times) * 1e3
