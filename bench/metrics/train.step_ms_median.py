"""Median milliseconds of the window's steps, each with its loss
read-back, by the host clock: the step time without the rare stalls of
whole seconds that ``train_tokens_per_s`` counts (a rate over all the
window's time).  A change to the step moves both; a stall moves only the
rate."""
import statistics


def read(run, trace, peaks):
    steps = run.counters.get("step_s")
    if not steps:
        return None
    return statistics.median(steps) * 1e3
