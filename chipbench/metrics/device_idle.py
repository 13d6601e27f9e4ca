"""Share of the traced window with no operation on the device (%)."""

from chipbench.readings import device_idle_pct


def read(r):
    return device_idle_pct(r)
