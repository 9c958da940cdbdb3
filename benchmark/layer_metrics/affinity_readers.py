"""Step body: policies of the window's sweep program whose kernel reads
`NodeState.aff_cnt`, as the sweep record says
(`SweepRecord.affinity_readers`, counted off the kernels' own
`reads_affinity`: 0 for FGD and every other built-in, 1 for GpuClustering);
median over the window's waves. It says WHY `affinity_in_scan` reads what
it reads. A program without the counter (the parent of the PR that brought
it) has nothing to read, and the metric is left out."""

from benchmark.layer_metrics.weight_rows import record_counter


def read(run):
    return record_counter(run, "affinity_readers")
