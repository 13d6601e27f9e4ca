"""Mean client-timed ``commit()`` latency over the window (ms)."""


def read(r):
    return sum(r.commit_ms) / len(r.commit_ms) if r.commit_ms else None
