"""Device time per step, per chip, of the spatial blocks' attention
forward, remat's recompute included: the ops under ``spatial/attn`` that
are not attention's backward (``chipbench.scopes``)."""
from chipbench import scopes


def read(m):
    return scopes.ms_per_step(
        m, lambda op: {"spatial", "attn"} <= op.scopes
        and "attn_bwd" not in op.scopes and not op.backward)
