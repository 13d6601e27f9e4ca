"""Mean acked write on the dispatcher (ms): the program's
``frontend.ingest`` spans of kind ``add`` (one ``add_documents`` batch,
durable through the WAL when it returns) in the traced window."""

from chipbench.spans import window_spans


def read(r):
    spans = window_spans(r) or ()
    adds = [s.end_ns - s.start_ns for s in spans
            if s.name == "frontend.ingest" and s.attrs.get("kind") == "add"]
    return 1e-6 * sum(adds) / len(adds) if adds else None
