"""Queries per coalesced wave over the window (front end)."""

from chipbench.readings import mean_wave


def read(r):
    return mean_wave(r)
