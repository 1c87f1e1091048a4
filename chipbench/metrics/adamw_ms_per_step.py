"""Device time per step, per chip, of the optimizer: the ops under
``adamw``, the global-norm clip included (``chipbench.scopes``)."""
from chipbench import scopes


def read(m):
    return scopes.ms_per_step(m, lambda op: "adamw" in op.scopes)
