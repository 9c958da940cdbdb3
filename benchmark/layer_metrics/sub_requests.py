"""Step body: Sub hypotheticals ONE column computation of the window's
sweep program evaluates a lane, as the sweep record says
(`SweepRecord.sub_requests`: the type set's distinct (gpu_milli, gpu_num)
requests on their bucket where every scoring kernel takes its whole-branch
pod types by request, the whole group's size where one goes type by type);
median over the window's waves. A program without the counter (the parent of
the PR that brought it) has nothing to read, and the metric is left out."""

from benchmark.layer_metrics.weight_rows import record_counter


def read(run):
    return record_counter(run, "sub_requests")
