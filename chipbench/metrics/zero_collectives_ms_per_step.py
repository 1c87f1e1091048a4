"""Device time per step, per chip, of ZeRO's collectives: every collective
that is not a planned DSP switch, read by kind because no collective
carries the program's ``zero`` scope (``chipbench.collectives``)."""
from chipbench import collectives, scopes


def read(m):
    return scopes.ms_per_step(m, collectives.is_zero)
