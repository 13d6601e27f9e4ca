"""Dispatcher host time per wave (ms): the program's ``frontend.wave``
span less its time under ``exec.fetch`` (the host waiting on the
device), averaged over the waves of the traced window."""

from chipbench.spans import host_ms_per_wave, window_spans


def read(r):
    spans = window_spans(r)
    return host_ms_per_wave(spans) if spans else None
