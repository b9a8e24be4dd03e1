"""Share of the HBM roofline reached by the fused yCHG programs, %.

Bytes the window's work must move (unpadded masks in, results out) at
peak bandwidth, over the device time of every op inside the
``jit_fused_analyze*`` programs."""

from observe import ychg_roofline_pct


def read(obs):
    return ychg_roofline_pct(obs)
