"""Fan-out host time per wave (ms): self time (children excluded) of the
program's ``sharded.shard`` and ``sharded.merge`` spans over the traced
window, per ``frontend.wave``."""

from chipbench.spans import self_ms_per_wave, window_spans


def read(r):
    spans = window_spans(r)
    return self_ms_per_wave(spans, ("sharded.shard", "sharded.merge")) \
        if spans else None
