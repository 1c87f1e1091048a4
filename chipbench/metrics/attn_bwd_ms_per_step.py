"""Device time per step, per chip, of attention's backward (the jnp
reference's vjp in the kernel's custom_vjp rule): the ops under
``attn_bwd`` (``chipbench.scopes``)."""
from chipbench import scopes


def read(m):
    return scopes.ms_per_step(m, lambda op: "attn_bwd" in op.scopes)
