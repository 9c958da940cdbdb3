"""Step body: policies of the window's sweep program whose normalizer runs
in its scan, as the sweep record says (`SweepRecord.normalized_policies`,
read off the policies' `normalize`: feasible extrema, scale and weighted
total every event; 0 for a raw-score family such as FGD alone, 1 for
PWR+FGD); median over the window's waves. A program without the counter
(the parent of the PR that brought it) has nothing to read, and the metric
is left out."""

from benchmark.layer_metrics.weight_rows import record_counter


def read(run):
    return record_counter(run, "normalized_policies")
