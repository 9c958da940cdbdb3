"""Step body: 1 where the window's sweep program adds into
`NodeState.aff_cnt` inside its event loop, 0 where it makes the leaf once a
chunk after its scan: 1 - `SweepRecord.affinity_deferred`, median over the
window's waves. A program whose kernel READS the counts every event
(GpuClustering) cannot defer them, so in its cell this reads 1 and a 0 is a
wrong program; lower is better only in the sense that the add is the scan's
largest single operation where it runs. A program without the counter (a
parent older than PR 42) has nothing to read, and the metric is left
out."""

from benchmark.layer_metrics.weight_rows import record_counter


def read(run):
    deferred = record_counter(run, "affinity_deferred")
    return None if deferred is None else 1 - deferred
