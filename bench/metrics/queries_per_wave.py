"""Serving layer: queries answered per device wave in the window
(``WaveScheduler.stats()``: completed over device waves)."""


def read(run):
    waves = run.stats["device_waves"]
    return run.stats["completed"] / waves if waves else None
