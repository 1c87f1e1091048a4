"""Device time per step, per chip, of the temporal blocks' attention
forward, remat's recompute included: the ops under ``temporal/attn`` that
are not attention's backward (``chipbench.scopes``)."""
from chipbench import scopes


def read(m):
    return scopes.ms_per_step(
        m, lambda op: {"temporal", "attn"} <= op.scopes
        and "attn_bwd" not in op.scopes and not op.backward)
