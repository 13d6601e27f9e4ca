"""Mean search-at-ack reopen (ms): the program's ``frontend.reopen``
spans (every shard's searcher rebound to its acked tail) in the traced
window."""

from chipbench.spans import window_spans


def read(r):
    spans = window_spans(r) or ()
    reopens = [s.end_ns - s.start_ns for s in spans
               if s.name == "frontend.reopen"]
    return 1e-6 * sum(reopens) / len(reopens) if reopens else None
