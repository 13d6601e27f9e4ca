"""Planner and stager host time per wave (ms): self time of the
program's ``plan.batch`` and ``exec.stage`` spans over the traced window,
per ``frontend.wave``."""

from chipbench.spans import self_ms_per_wave, window_spans


def read(r):
    spans = window_spans(r)
    return self_ms_per_wave(spans, ("plan.batch", "exec.stage")) \
        if spans else None
