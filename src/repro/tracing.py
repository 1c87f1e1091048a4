"""The names the train step's stages carry inside the program.

A scope (``scope(name)``, which is ``jax.named_scope``) adds ``name`` to
the ``metadata.op_name`` of every HLO op traced inside it; XLA keeps it on
the compiled ops and the profiler reports it with each device op.  It costs
nothing at run time.  Name stacks survive ``jvp``, ``transpose``,
``checkpoint`` and ``scan``: a backward op's name carries a ``transpose(``
wrapper, and remat's recompute sits under ``rematted_computation``.  Ops the
SPMD partitioner inserts (resharding all-to-alls, ZeRO gathers) inherit the
scope of the op they serve: a weight gather that of the dot it feeds, a
gradient reduce-scatter that of the backward's dot, or none.  So ``zero``
names where a layer's ZeRO-sharded weights leave their stack, not the
collectives: naming those would take a sharding constraint, and any
constraint on the weights re-lays out the step.

A span (``span(name)``, which is ``jax.profiler.TraceAnnotation``) marks an
interval of host time in the profiler's trace, on the device trace's clock.
"""
from __future__ import annotations

import jax

# -- scopes: stages of the DiT train step ------------------------------------
LAYERS = "layers"        # the loop over the blocks: each layer's weights
                         # sliced from the stack, their gradients stacked back
SPATIAL = "spatial"      # a block attending over S (models/transformer2d.py)
TEMPORAL = "temporal"    # a block attending over T
ADALN = "adaln"          # norms and adaLN modulation
PROJ = "proj"            # q, k, v and o projections
ATTN = "attn"            # the attention kernel with its pads and transposes
MLP = "mlp"              # the FFN
ATTN_BWD = "attn_bwd"    # attention's backward (kernels/ops.py)
ADAMW = "adamw"          # the optimizer, global-norm clip included
EMBED = "embed"          # patch, position and timestep embeddings
LOSS = "loss"            # final norm, head and the MSE
DSP_SWITCH = "dsp_switch"  # every planned layout transition (core/schedule)
ZERO = "zero"            # on a mesh: a layer's ZeRO-sharded weights sliced
                         # from the stack, their gradients stacked back

SCOPES = (LAYERS, SPATIAL, TEMPORAL, ADALN, PROJ, ATTN, MLP, ATTN_BWD, ADAMW,
          EMBED, LOSS, DSP_SWITCH, ZERO)
ATTN_XLA = "attn_xla"    # inside attn, not a stage: XLA's forward, no kernel

# -- spans: host time of one training step (train/trainer.py) ----------------
STEP = "train"           # the StepTraceAnnotation around each step
DATA = "data"            # the next batch
DISPATCH = "dispatch"    # the jitted step's call
SYNC = "sync"            # waiting for the step's loss
CHECKPOINT = "checkpoint"

scope = jax.named_scope
span = jax.profiler.TraceAnnotation
