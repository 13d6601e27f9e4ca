"""Mean wait of a query for its wave (ms): the program's
``frontend.queue`` spans, submit to the dispatcher's pop, of the queries
picked up in the traced window."""

from chipbench.spans import queue_wait_ms, window_spans


def read(r):
    spans = window_spans(r)
    return queue_wait_ms(spans) if spans else None
