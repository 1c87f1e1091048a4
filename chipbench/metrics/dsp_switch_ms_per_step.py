"""Device time per step, per chip, of the planned DSP switches: the
all-to-alls under ``dsp_switch`` (forward and remat's recompute) and on
the backward's ``spatial``/``temporal`` block-end anchors
(``chipbench.collectives``)."""
from chipbench import collectives, scopes


def read(m):
    return scopes.ms_per_step(m, collectives.is_switch)
