"""Of the planned DSP switches' device time, the part per step, per chip,
in which no compute op ran on the same chip: the switch time that no
overlap hides (``chipbench.collectives``)."""
from chipbench import collectives


def read(m):
    return collectives.exposed_switch_ms_per_step(m)
