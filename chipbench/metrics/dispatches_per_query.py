"""Executor device dispatches per served query over the window."""

from chipbench.readings import dispatches_per_query


def read(r):
    return dispatches_per_query(r)
