"""Lane slicing and readback: from the `fetch` span's mark `ready` (the
device has finished its last program of the wave) to the end of the
`slice_lanes` span, as the sweep record derives it
(`SweepRecord.host_tail_s`: copy, unpack, per-lane slicing), median over
the window's waves. Nothing runs on the device meanwhile, so nothing hides
it. A program without the field (the parent of the PR that brought it)
has nothing to read, and the metric is left out."""

from benchmark.layer_metrics.host_lead_s import derived_seconds


def read(run):
    return derived_seconds(run, "host_tail_s")
