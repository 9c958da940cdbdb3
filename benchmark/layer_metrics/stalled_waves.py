"""Device: waves of the window whose wall is over 1.5 x the window's
median (`drivers/wave.STALL`), counted here from the walls every wave
driver returns. Such a wave is correct and stays in every end-to-end
metric: `lane_events_per_s`, a sum over the window, carries it and
`wave_s`, a median, does not, so a run whose rate reads low beside a level
`wave_s` is explained here. Every one caught so far was the fetch's wait
for the device (`device_wait_s`; PERF.md section 7). The untraced run
prints the same count in its line's `window`. Listed for every cell: a
driver kind has to return `waves` with a `wall_s` each."""

from benchmark.drivers import wave


def read(run):
    walls = [w["wall_s"] for w in run.get("waves") or []]
    return wave.stalled_waves(walls) if walls else None
