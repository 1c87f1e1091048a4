"""Device time per step, per chip, of the FFN: the ops under ``mlp``,
forward, remat's recompute and backward (``chipbench.scopes``)."""
from chipbench import scopes


def read(m):
    return scopes.ms_per_step(m, lambda op: "mlp" in op.scopes)
