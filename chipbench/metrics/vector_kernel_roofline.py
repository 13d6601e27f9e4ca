"""Dense-vector top-k against the HBM roofline (%): the bytes the waves
need (``roofline.vector_wave_bytes``, one read of the vector column per
wave) over the device time of the ``_fused_vector`` programs times the
chip's peak HBM bandwidth."""

from chipbench.readings import program_seconds
from chipbench.roofline import vector_wave_bytes


def read(r):
    t = program_seconds(r, "_fused_vector")
    if not t or not r.vector_waves or r.peaks is None:
        return None
    need = r.vector_waves * vector_wave_bytes(r.vector_rows, r.vector_dim)
    return 100.0 * need / (t * r.peaks["hbm_bytes_per_s"])
