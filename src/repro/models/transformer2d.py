"""The paper's base model: OpenSora-like 2D (spatial-temporal) DiT.

Input is a latent video tensor ``x: (B, T, S, C_in)`` (the VAE/patch frontend
is a stub — input_specs() supplies patched latents) plus a diffusion timestep
``t: (B,)`` for adaLN modulation.  Blocks alternate: a *spatial*
block (attention over S, independent across B,T) then a *temporal* block
(attention over T, independent across B,S) — Equation 4/5 of the paper with
K=2.  ``n_layers`` counts blocks (the paper's "layer" = one spatial + one
temporal block pair): 28 blocks at d=1152 gives the 720M model, 36 blocks at
d=2048 the 3B model (Table 4; "2038" is a transcription artifact of 2048).

Parallel modes (paper §4, Appendix A.2), all sharing one parameter pytree:

  dsp        sequence sharded on T; ONE all-to-all switch (T<->S) at each
             stage boundary => 2 switches, 2M/N volume per layer.
  ulysses    sharded on T; temporal attention does 4 all-to-alls
             (q,k,v seq->head + out head->seq) => 4M/N per layer.
  megatron   sharded on T; every block all-gathers the full sequence in and
             reduce-scatters out => 8 collectives, 8M per layer.
  ring       sharded on T; temporal attention rotates K/V around the ring
             (collective_permute) => 2M per layer.

The explicit (shard_map) implementations live in ``make_spmd_forward``; the
compiler path (``forward``) expresses DSP as layout constraints and is what
the production launcher lowers.  BOTH DSP paths execute the SAME planned
switching schedule (``stages``/``dsp_schedule`` -> ``core.plan`` solver)
through the ``core.schedule.ScheduleExecutor`` — this module declares stages
and never issues a switch or stage-boundary constraint itself.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import tracing
from repro.core import ring as ring_core
from repro.core import ulysses as ulysses_core
from repro.core import megatron_sp as megatron_core
from repro.core.layout import from_mesh
from repro.core.plan import Stage, pair_placement_equal, plan_switches_2d
from repro.core.schedule import (PeriodicSchedule, Schedule2D,
                                 ScheduleExecutor, ScheduleExecutor2D,
                                 UnrolledSchedule, plan_joint_schedule,
                                 plan_schedule, plan_strategy_schedule,
                                 plan2d_schedule)
from repro.kernels.ops import flash_attention
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class T2DConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    in_dim: int = 64                  # stub latent/patch feature size
    head_dim: Optional[int] = None
    mlp_kind: str = "gelu"            # paper's FFN is 2-layer w/ activation
    modulate: bool = True             # DiT adaLN-zero timestep modulation
    dtype: Any = jnp.bfloat16
    n_kv_heads: Optional[int] = None  # GQA: K/V head count (None = MHA)

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def kvh(self) -> int:
        return self.n_kv_heads or self.n_heads


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: T2DConfig):
    ks = jax.random.split(key, 6)
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.dh
    p = {
        "ln1": L.init_norm(d, dtype=cfg.dtype),
        "wq": L.init_linear(ks[0], d, h * dh, dtype=cfg.dtype),
        "wk": L.init_linear(ks[1], d, cfg.kvh * dh, dtype=cfg.dtype),
        "wv": L.init_linear(ks[2], d, cfg.kvh * dh, dtype=cfg.dtype),
        "wo": L.init_linear(ks[3], h * dh, d, dtype=cfg.dtype),
        "ln2": L.init_norm(d, dtype=cfg.dtype),
        "mlp": L.init_mlp(ks[4], d, cfg.d_ff, kind=cfg.mlp_kind,
                          dtype=cfg.dtype),
    }
    if cfg.modulate:
        p["mod"] = L.init_modulation(ks[5], d, dtype=cfg.dtype)
    return p


def init_t2d(key, cfg: T2DConfig):
    assert cfg.n_layers % 2 == 0, "blocks alternate spatial/temporal"
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def one_layer(k):
        ka, kb = jax.random.split(k)
        return {"spatial": _init_block(ka, cfg),
                "temporal": _init_block(kb, cfg)}

    layer_keys = jax.random.split(k1, cfg.n_layers // 2)
    params = {
        "layers": jax.vmap(one_layer)(layer_keys),
        "embed": L.init_patch_embed(k2, cfg.in_dim, cfg.d_model,
                                    dtype=cfg.dtype),
        "final_norm": L.init_norm(cfg.d_model, dtype=cfg.dtype),
        "head": L.init_linear(k3, cfg.d_model, cfg.in_dim, bias=True,
                              dtype=cfg.dtype),
        "t_proj": L.init_linear(k4, cfg.d_model, cfg.d_model, bias=True,
                                dtype=cfg.dtype),
    }
    return params


def t2d_param_count(cfg: T2DConfig) -> int:
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.dh
    per_block = (d * h * dh * 2 + d * cfg.kvh * dh * 2
                 + L.mlp_param_count(d, cfg.d_ff, cfg.mlp_kind))
    if cfg.modulate:
        per_block += d * 6 * d
    return cfg.n_layers * per_block + 2 * cfg.in_dim * d + d * d


# ---------------------------------------------------------------------------
# DSP stage declaration + planned switching schedule
# ---------------------------------------------------------------------------

def stages(cfg: T2DConfig, *, t_len: Optional[int] = None,
           s_len: Optional[int] = None, batch: Optional[int] = None,
           grad_dtype_bytes: Optional[int] = None):
    """Declare the model's stage sequence for the switching planner, in
    EXECUTION order: per layer one spatial block (computes along S = dim 2,
    so the shard must sit on T) then one temporal block (computes along
    T = dim 1).  Tensors are (B, T, S, C); with extents given, each stage
    carries the global activation shape so the planner prices transitions in
    paper-Table-2 bytes.  ``grad_dtype_bytes`` declares the width of the
    gradients crossing the same boundaries backward (joint fwd+bwd
    planning; defaults to the activation dtype)."""
    shape = None
    kv = None
    if None not in (t_len, s_len, batch):
        shape = (batch, t_len, s_len, cfg.d_model)
        # K + V activations of one attention (the payload embedded
        # strategies stream or head-scatter; GQA shrinks it)
        kv = 2.0 * batch * t_len * s_len * cfg.kvh * cfg.dh
    db = jnp.dtype(cfg.dtype).itemsize
    out = []
    for i in range(cfg.n_layers // 2):
        out.append(Stage(frozenset({2}), f"layer{i}.spatial", shape, db,
                         bwd_dtype_bytes=grad_dtype_bytes,
                         kv_bytes=None if kv is None else kv * db,
                         kv_heads=cfg.kvh))
        out.append(Stage(frozenset({1}), f"layer{i}.temporal", shape, db,
                         bwd_dtype_bytes=grad_dtype_bytes,
                         kv_bytes=None if kv is None else kv * db,
                         kv_heads=cfg.kvh))
    return out


def dsp_schedule(cfg: T2DConfig, n: int, *, t_len: Optional[int] = None,
                 s_len: Optional[int] = None, batch: Optional[int] = None,
                 initial: int = 1, topology=None, joint: bool = False,
                 grad_dtype_bytes: Optional[int] = None,
                 overlap: Optional[str] = None):
    """Solve the switching plan for this model (enter sharded on T, return
    to T for the loss/head).  Returns the scan-body ``PeriodicSchedule``
    when the plan repeats with the 2-stage layer period, else the
    ``UnrolledSchedule`` view (``forward`` python-unrolls the layer loop
    for those).

    ``joint=True`` additionally plans the backward pass as its own stage
    graph (``core.plan.plan_joint``): the returned schedule carries
    ``bwd_dims`` when a non-mirrored round trip is strictly cheaper —
    priced in seconds on ``topology`` when one is given.

    Both dims stay candidates regardless of divisibility: with only two
    sequence dims and each stage forbidding one, excluding either leaves
    some stage infeasible — non-divisible extents are instead handled
    downstream (the auto path pads; the explicit path rejects them in
    ``dynamic_switch``).

    ``overlap`` ("chunked" | "double_buffer") attaches per-stage roofline
    compute estimates (``analysis.roofline.attach_compute_seconds``), has
    the solver price switches at their EXPOSED seconds, and stamps the mode
    on the schedule so the explicit executor decomposes each planned switch
    into compute-interleaved ``ppermute`` hops."""
    st = stages(cfg, t_len=t_len, s_len=s_len, batch=batch,
                grad_dtype_bytes=grad_dtype_bytes)
    if overlap is not None:
        from repro.analysis.roofline import attach_compute_seconds
        st = attach_compute_seconds(
            st, cfg, topology if topology is not None else max(n, 1))
    solve = plan_joint_schedule if joint else plan_schedule
    sched = solve(st, [1, 2], n=max(n, 1), initial=initial, final=initial,
                  topology=topology, overlap=overlap)
    try:
        return sched.periodic(2)
    except ValueError:
        return sched.unrolled()


def strategy_schedule(cfg: T2DConfig, n: int, *, t_len: Optional[int] = None,
                      s_len: Optional[int] = None, batch: Optional[int] = None,
                      initial: int = 1, topology=None,
                      overlap: Optional[str] = None):
    """Solve the unified (stage, dim, strategy) plan for this model
    (``core.schedule.plan_strategy_schedule``) — on a uniform/absent
    topology this IS ``dsp_schedule``'s plan (all-"dsp", bit-for-bit); on a
    tiered fabric stages may come back with embedded strategies, e.g. the
    ICI x DCN hybrid (ring over DCN x a2a inside ICI) at temporal stages.
    Returns the scan-body ``PeriodicSchedule`` when the plan repeats with
    the 2-stage layer period, else the ``UnrolledSchedule`` view."""
    st = stages(cfg, t_len=t_len, s_len=s_len, batch=batch)
    if overlap is not None:
        from repro.analysis.roofline import attach_compute_seconds
        st = attach_compute_seconds(
            st, cfg, topology if topology is not None else max(n, 1))
    sched = plan_strategy_schedule(st, [1, 2], n=max(n, 1), initial=initial,
                                   final=initial, topology=topology,
                                   overlap=overlap)
    try:
        return sched.periodic(2)
    except ValueError:
        return sched.unrolled()


# in-period stage index by the block's compute axis (spatial computes S=2)
_STAGE_OF_AXIS = {2: 0, 1: 1}


# ---------------------------------------------------------------------------
# 2D (TSP-fold) stage declaration + planned schedule — layouts are dim
# PAIRS on an ("sp_out", "sp_in") mesh (launch.mesh.make_sp2d_mesh):
# component k of a layout shards one tensor dim over grid axis k, so the
# planner can put the sequence on one axis and the head/channel dim on the
# other (seq x tensor, the Zyphra TSP fold) and each boundary pays one
# sub-axis all-to-all per CHANGED axis only.
# ---------------------------------------------------------------------------

# stage-view (B, T, S, C) dim -> tensor dim of the execution tensors the
# planned boundaries actually constrain (ScheduleExecutor2D ``dims`` maps):
_QKV_DIMS = {1: 2, 2: 3, 3: 4}     # stacked qkv (3, B, T, S, H, dh) — the
                                   # stage view's dim 3 (C) lands on the
                                   # HEAD axis: extents declare its
                                   # divisibility unit is n_heads
_O_DIMS = {1: 1, 2: 2, 3: 3}       # attention out (B, T, S, H, dh)


def stages2d(cfg: T2DConfig, *, t_len: Optional[int] = None,
             s_len: Optional[int] = None, batch: Optional[int] = None):
    """Declare the FOUR-stage-per-layer sequence the 2D planner consumes.

    Unlike the 1D ``stages`` (which never considers sharding C), the
    attention cores are split out from the projection/norm/MLP regions:
    a core is head-independent, so the flat channel dim (3) is a legal
    shard BY HEAD for it — ``Stage.extents`` declares dim 3's divisibility
    unit is ``n_heads``, not ``d_model``.  The surrounding regions compute
    along C (projections, norms, MLP) and declare ``compute_dims={3}``, so
    no feasible layout ever shards C there — which is exactly what forces
    every collective onto a planned boundary (zero collectives inside
    stages, the compiled contract of the (2,4) md_scenario)."""
    shape = None
    ext = None
    if None not in (t_len, s_len, batch):
        shape = (batch, t_len, s_len, cfg.d_model)
        ext = (batch, t_len, s_len, cfg.n_heads)
    db = jnp.dtype(cfg.dtype).itemsize
    out = []
    for i in range(cfg.n_layers // 2):
        out.append(Stage(frozenset({2}), f"layer{i}.sp_attn", shape, db,
                         extents=ext))
        out.append(Stage(frozenset({3}), f"layer{i}.sp_mlp", shape, db,
                         extents=ext))
        out.append(Stage(frozenset({1}), f"layer{i}.t_attn", shape, db,
                         extents=ext))
        out.append(Stage(frozenset({3}), f"layer{i}.t_mlp", shape, db,
                         extents=ext))
    return out


def dsp2d_schedule(cfg: T2DConfig, grid, *, t_len: Optional[int] = None,
                   s_len: Optional[int] = None, batch: Optional[int] = None,
                   initial=(1, 2), topology=None):
    """Solve the 2D switching plan (enter/exit with T on the outer axis and
    S on the inner — the natural dataloader fold of ``make_sp2d_mesh``:
    each sp_out slice holds a contiguous T block, sliced along S inside).
    Returns the period-4 ``PeriodicSchedule2D`` scan-body view.  On a
    degenerate ``(n, 1)``/``(1, n)`` grid the planner delegates to the 1D
    DP, so this collapses to today's plans bit-for-bit."""
    st = stages2d(cfg, t_len=t_len, s_len=s_len, batch=batch)
    # Solve ONE period with entry = exit = the carried layout: because every
    # stage holds the same activation shape, the exit transition prices
    # exactly the wrap back into the next period, so this IS the steady
    # state — and tiling keeps the plan periodic even when the unrolled
    # DP's tie-breaks would drift (equal-cost plans need not repeat).
    body = plan_switches_2d(st[:4], [1, 2, 3], grid=tuple(grid),
                            initial=initial, final=initial,
                            topology=topology)
    sched = Schedule2D(tuple(st), tuple(body) * (len(st) // 4),
                       grid=tuple(grid), initial=initial, final=initial,
                       topology=topology)
    return sched.periodic(4)


def forward2d(params, x, t, cfg: T2DConfig, *, mesh: Mesh,
              backend: str = "ref", remat: bool = True, topology=None,
              schedule=None):
    """2D-layout compiler-path forward on an ("sp_out", "sp_in") mesh.

    x: (B, T, S, C_in) global.  The planned ``Schedule2D`` drives every
    boundary through ``ScheduleExecutor2D``; XLA lowers each single-axis
    layout change to ONE all-to-all over just that grid axis, and unchanged
    axes compile to nothing.  The residual stream is carried at the
    mlp-stage layout (steady state, e.g. T over sp_out x S over sp_in); the
    attention-core layouts live strictly INSIDE the block — the planned
    switch into a core lands on the stacked (3, B, T, S, H, dh) q/k/v
    tensor (one fused constraint -> one a2a, the 1D ``heads_stacked``
    idiom), so MHA is required; the switch out lands on the attention
    output before ``wo``.  Bit-identical to the 1D ``forward`` reference on
    any grid (layout changes never change the math)."""
    if cfg.kvh != cfg.n_heads:
        raise ValueError("forward2d stacks q/k/v for the fused planned "
                         "switch and needs MHA (n_kv_heads == n_heads)")
    missing = [a for a in ("sp_out", "sp_in") if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"forward2d needs the 2D SP mesh of launch.mesh.make_sp2d_mesh "
            f"(axes ('sp_out', 'sp_in')); missing {missing}")
    grid = (mesh.shape["sp_out"], mesh.shape["sp_in"])
    dp_axes = tuple(a for a in mesh.axis_names
                    if a not in ("sp_out", "sp_in"))
    psched = schedule if schedule is not None else dsp2d_schedule(
        cfg, grid, t_len=x.shape[1], s_len=x.shape[2], batch=x.shape[0],
        topology=topology)
    ex = ScheduleExecutor2D(psched, backend="auto", mesh=mesh,
                            dp_axes=dp_axes)
    initial = psched.schedule.initial
    final = (psched.schedule.final if psched.schedule.final is not None
             else psched.layouts[-1])
    if not pair_placement_equal(psched.layouts[-1], initial, grid):
        raise ValueError(
            f"forward2d carries the residual at the last in-period layout "
            f"and enters at the schedule's initial; the plan ends its "
            f"period at {psched.layouts[-1]} but enters at {initial} — "
            f"pass initial equal to the steady-state mlp layout")

    x = L.patch_embed(params["embed"], x)
    x = add_pos_embed(x, cfg, 0, 0)
    x = ex.constrain(x, initial)        # dataloader layout (a keep)
    t_emb = None
    if cfg.modulate and t is not None:
        t_emb = L.linear(params["t_proj"],
                         L.timestep_embedding(t, cfg.d_model).astype(x.dtype))

    def half_block(p, xc, *, axis, enter_fn, exit_idx):
        # one block at the carried mlp layout; ``enter_fn`` applies the
        # planned switch into the attention core (on stacked qkv),
        # ``exit_idx`` the in-period stage whose layout the core exits to
        b, t_, s_, _ = xc.shape
        hh, dh = cfg.n_heads, cfg.dh
        mod = _mod6(p, t_emb, cfg)

        def bmod(m):
            return m[:, :, None, :].astype(xc.dtype)

        h = L.rms_norm(p["ln1"], xc)
        if mod is not None:
            h = _modulate(h, bmod(mod[0]), bmod(mod[1]))
        # ONE fused qkv projection: the planned switch constrains the
        # stacked tensor, and with a single producing matmul GSPMD lands a
        # single all-to-all on it — three separate linears under a stack
        # would have the sharding pushed back through the stack onto each
        # operand (three a2as, breaking the one-per-changed-axis contract)
        wqkv = jnp.concatenate([p["wq"]["w"], p["wk"]["w"], p["wv"]["w"]],
                               axis=1)
        qkv = h @ wqkv
        if "b" in p["wq"]:
            qkv = qkv + jnp.concatenate([p["wq"]["b"], p["wk"]["b"],
                                         p["wv"]["b"]])
        qkv = qkv.reshape(b, t_, s_, 3, hh, dh).transpose(3, 0, 1, 2, 4, 5)
        qkv = enter_fn(qkv)
        q, k, v = qkv[0], qkv[1], qkv[2]
        # fold the non-attended seq dim into the attention batch with the
        # SHARDED factor MAJOR — the only merge order GSPMD can represent
        # for a sharded factor (minor-factor merges force involuntary full
        # rematerialization); fold_anchor pins the composite entry
        attn_i = exit_idx - 1
        if axis == 1:      # temporal: attend over T, batch (S, B, H)
            fold_dims = {2: 0, 1: 1, 3: 2}

            def fold(y):
                y = y.transpose(2, 0, 1, 3, 4).reshape(s_ * b, t_, hh, dh)
                return ex.fold_anchor(y, attn_i, dims=fold_dims)

            def unfold(y):
                return y.reshape(s_, b, t_, hh, dh).transpose(1, 2, 0, 3, 4)
        else:              # spatial: attend over S, batch (T, B, H)
            fold_dims = {1: 0, 2: 1, 3: 2}

            def fold(y):
                y = y.transpose(1, 0, 2, 3, 4).reshape(t_ * b, s_, hh, dh)
                return ex.fold_anchor(y, attn_i, dims=fold_dims)

            def unfold(y):
                return y.reshape(t_, b, s_, hh, dh).transpose(1, 0, 2, 3, 4)
        o = unfold(_default_attn(backend)(fold(q), fold(k), fold(v)))
        o = ex.boundary(o, exit_idx, dims=_O_DIMS)   # planned switch back
        o = L.linear(p["wo"], o.reshape(b, t_, s_, hh * dh))
        if mod is not None:
            o = o * bmod(mod[2])
        xc = ex.anchor(xc + o, exit_idx)
        h = L.rms_norm(p["ln2"], xc)
        if mod is not None:
            h = _modulate(h, bmod(mod[3]), bmod(mod[4]))
        h = L.mlp(p["mlp"], h, cfg.mlp_kind)
        if mod is not None:
            h = h * bmod(mod[5])
        return ex.anchor(xc + h, exit_idx)

    def layer_body(xc, lp):
        # the switch into stage 0 (sp_attn) is the period's wrap: the carry
        # stays at the mlp layout across iterations and the first boundary
        # executes inside the block, on the stacked qkv
        xc = half_block(lp["spatial"], xc, axis=2, exit_idx=1,
                        enter_fn=lambda y: ex.wrap(y, dims=_QKV_DIMS,
                                                   batch_dim=1))
        xc = half_block(lp["temporal"], xc, axis=1, exit_idx=3,
                        enter_fn=lambda y: ex.boundary(y, 2, dims=_QKV_DIMS,
                                                       batch_dim=1))
        return xc, None

    body = (jax.checkpoint(layer_body, prevent_cse=False) if remat
            else layer_body)
    from repro.models.flags import scan_or_unroll
    x, _ = scan_or_unroll(body, x, params["layers"])
    x = ex.constrain(x, final)          # planned exit (a keep)
    x = L.rms_norm(params["final_norm"], x)
    return L.linear(params["head"], x)


# ---------------------------------------------------------------------------
# Positional encoding (sinusoidal, offset-aware for sharded dims)
# ---------------------------------------------------------------------------

def _sincos(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) *
                    jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def add_pos_embed(x, cfg: T2DConfig, t_offset=0, s_offset=0):
    """x: (B, T, S, C) local view; offsets give global positions of the
    local shard (explicit path passes axis_index * local_len)."""
    _, t, s, c = x.shape
    pe_t = _sincos(t_offset + jnp.arange(t), c)          # (T, C)
    pe_s = _sincos(s_offset + jnp.arange(s), c)          # (S, C)
    return x + pe_t[None, :, None, :].astype(x.dtype) \
             + pe_s[None, None, :, :].astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

AttnImpl = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


def _default_attn(backend: str) -> AttnImpl:
    def impl(q, k, v):
        # q: (B', L, H, D); k/v may carry fewer (GQA) heads -> repeat them
        # up to H locally (the kernel wants equal head counts)
        rep = q.shape[2] // k.shape[2]
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=False,
                            backend=backend)
        return o.transpose(0, 2, 1, 3)
    return impl


def _mod6(p, t_emb, cfg: T2DConfig):
    if not cfg.modulate or t_emb is None:
        return None
    with tracing.scope(tracing.ADALN):
        return L.modulation(p["mod"], t_emb)     # 6 x (B, 1, C)


def _modulate(h, shift, scale):
    return h * (1.0 + scale) + shift


def t2d_block(p, x, cfg: T2DConfig, *, axis: int, t_emb=None,
              attn_impl: Optional[AttnImpl] = None, backend: str = "pallas",
              fold_hook=None, stage_hook=None):
    """One transformer block computing attention along ``axis`` (1=T, 2=S)
    of x: (B, T, S, C).  The other sequence dim folds into the batch as the
    MINOR factor of (B*other) so batch stays the sharded MAJOR factor and
    SPMD layouts survive the reshape; ``fold_hook`` (auto path) re-asserts
    the composite sharding."""
    attn_impl = attn_impl or _default_attn(backend)
    b, t, s, c = x.shape
    h_heads, dh = cfg.n_heads, cfg.dh

    def fold(y):       # (B, T, S, C) -> (B*other, L, C)
        if axis == 1:
            y = y.transpose(0, 2, 1, 3).reshape(b * s, t, c)
        else:
            y = y.reshape(b * t, s, c)
        return fold_hook(y) if fold_hook is not None else y

    def unfold(y):
        if axis == 1:
            return y.reshape(b, s, t, c).transpose(0, 2, 1, 3)
        return y.reshape(b, t, s, c)

    def bmod(m):       # (B, 1, C) -> (B, 1, 1, C)
        return m[:, :, None, :].astype(x.dtype)

    def anchor(y):
        # pin every intra-block 4D tensor to the stage layout: without these
        # anchors XLA's backward sharding propagation flips layouts mid-block
        # and re-shards the 4x-wide MLP hidden in f32 (found in the t2d HLO
        # audit — hundreds of GB of spurious all-to-alls)
        return stage_hook(y, axis) if stage_hook is not None else y

    with tracing.scope(tracing.TEMPORAL if axis == 1 else tracing.SPATIAL):
        mod = _mod6(p, t_emb, cfg)
        with tracing.scope(tracing.ADALN):
            h = L.rms_norm(p["ln1"], x)
            if mod is not None:
                h = _modulate(h, bmod(mod[0]), bmod(mod[1]))
        h = anchor(h)
        hf = fold(h)
        l = hf.shape[1]
        with tracing.scope(tracing.PROJ):
            q = L.linear(p["wq"], hf).reshape(-1, l, h_heads, dh)
            k = L.linear(p["wk"], hf).reshape(-1, l, cfg.kvh, dh)
            v = L.linear(p["wv"], hf).reshape(-1, l, cfg.kvh, dh)
        with tracing.scope(tracing.ATTN):
            o = attn_impl(q, k, v).reshape(-1, l, h_heads * dh)
        with tracing.scope(tracing.PROJ):
            o = L.linear(p["wo"], o)
        o = anchor(unfold(o))
        if mod is not None:
            with tracing.scope(tracing.ADALN):
                o = o * bmod(mod[2])
        x = anchor(x + o)

        with tracing.scope(tracing.ADALN):
            h = L.rms_norm(p["ln2"], x)
            if mod is not None:
                h = _modulate(h, bmod(mod[3]), bmod(mod[4]))
        h = anchor(h)
        with tracing.scope(tracing.MLP):
            h = L.mlp(p["mlp"], h, cfg.mlp_kind)
        h = anchor(h)
        if mod is not None:
            with tracing.scope(tracing.ADALN):
                h = h * bmod(mod[5])
        return anchor(x + h)


def _megatron_block(p, x, cfg: T2DConfig, *, axis: int, t_emb=None,
                    axis_name: str = "model", backend: str = "pallas"):
    """Megatron-SP layout: x arrives sharded along T (dim 1).  AllGather the
    sequence, compute attention/MLP with locally-sliced heads / hidden
    (tensor parallel), ReduceScatter partial outputs back.  4 collectives,
    volume 4M per block (8M per 2-block layer)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_loc, s, c = x.shape
    h_heads, dh = cfg.n_heads, cfg.dh
    assert h_heads % n == 0, "Megatron-SP requires heads % tp == 0"
    h_loc = h_heads // n
    mod = _mod6(p, t_emb, cfg)

    def bmod(m):
        return m[:, :, None, :].astype(x.dtype)

    def slice_cols(w, parts):       # column-parallel slice of (d_in, d_out)
        size = w.shape[1] // parts
        return jax.lax.dynamic_slice_in_dim(w, idx * size, size, axis=1)

    def slice_rows(w, parts):
        size = w.shape[0] // parts
        return jax.lax.dynamic_slice_in_dim(w, idx * size, size, axis=0)

    # ---- attention: AG -> TP attention -> RS
    h = L.rms_norm(p["ln1"], x)
    if mod is not None:
        h = _modulate(h, bmod(mod[0]), bmod(mod[1]))
    hg = megatron_core.allgather_seq(h, seq_dim=1, axis_name=axis_name)
    t = hg.shape[1]

    def fold(y):
        if axis == 1:
            return y.transpose(0, 2, 1, 3).reshape(b * s, t, -1)
        return y.reshape(b * t, s, -1)

    def unfold(y, cdim):
        if axis == 1:
            return y.reshape(b, s, t, cdim).transpose(0, 2, 1, 3)
        return y.reshape(b, t, s, cdim)

    hf = fold(hg)
    l = hf.shape[1]
    q = (hf @ slice_cols(p["wq"]["w"], n)).reshape(-1, l, h_loc, dh)
    k = (hf @ slice_cols(p["wk"]["w"], n)).reshape(-1, l, h_loc, dh)
    v = (hf @ slice_cols(p["wv"]["w"], n)).reshape(-1, l, h_loc, dh)
    o = _default_attn(backend)(q, k, v).reshape(-1, l, h_loc * dh)
    o_part = o @ slice_rows(p["wo"]["w"], n)            # partial sum
    o_part = unfold(o_part, c)
    o = megatron_core.reduce_scatter_seq(o_part, seq_dim=1,
                                         axis_name=axis_name)
    if mod is not None:
        o = o * bmod(mod[2])
    x = x + o

    # ---- MLP: AG -> TP mlp -> RS
    h = L.rms_norm(p["ln2"], x)
    if mod is not None:
        h = _modulate(h, bmod(mod[3]), bmod(mod[4]))
    hg = megatron_core.allgather_seq(h, seq_dim=1, axis_name=axis_name)
    wi = slice_cols(p["mlp"]["wi"]["w"], n)
    wo = slice_rows(p["mlp"]["wo"]["w"], n)
    act = jax.nn.gelu if cfg.mlp_kind == "gelu" else jax.nn.relu
    hh = act(hg @ wi) @ wo
    hh = megatron_core.reduce_scatter_seq(hh, seq_dim=1, axis_name=axis_name)
    if mod is not None:
        hh = hh * bmod(mod[5])
    return x + hh


# ---------------------------------------------------------------------------
# Full forward — local/auto path
# ---------------------------------------------------------------------------

def forward(params, x, t, cfg: T2DConfig, *, mesh: Optional[Mesh] = None,
            mode: str = "dsp", backend: str = "pallas", remat: bool = True,
            remat_group: int = 2, t_offset=0, s_offset=0,
            topology=None, joint: bool = False, schedule=None,
            overlap: Optional[str] = None):
    """Compiler-path forward.  x: (B, T, S, C_in) global; with a mesh given,
    the planned DSP schedule (``dsp_schedule``) drives every stage-boundary
    layout change through the auto-backend ScheduleExecutor; XLA lowers each
    boundary constraint change to one all-to-all (the dynamic switch).

    ``joint=True`` plans the backward pass too (priced on ``topology`` when
    given): the executor then emits every boundary through a custom_vjp so
    the backward runs its own planned switch sequence.  ``schedule``
    overrides the solved plan with a caller-provided ``PeriodicSchedule`` /
    ``UnrolledSchedule``; non-periodic (unrolled) schedules python-unroll
    the layer loop instead of scanning.

    ``overlap`` makes the PLAN overlap-aware (exposed-seconds pricing; the
    mode and hide budgets land on the schedule for metas/benchmarks) but
    this auto path still emits sharding constraints — decomposed,
    compute-interleaved switches need the explicit backend
    (``make_spmd_forward(..., overlap=...)``); here any hiding is up to
    XLA's collective pipeliner."""
    ex = ScheduleExecutor.null()
    fold_hook = None
    stage_hook = None
    attn_impl = None
    psched = None
    if mesh is not None and mode == "dsp":
        ctx = from_mesh(mesh)
        psched = schedule if schedule is not None else dsp_schedule(
            cfg, ctx.sp_size, t_len=x.shape[1], s_len=x.shape[2],
            batch=x.shape[0], topology=topology, joint=joint,
            overlap=overlap)
        ex = ScheduleExecutor(psched, backend="auto", ctx=ctx)

        def fold_hook(y):
            # folded (B*other, L, C): batch major over dp, sharded seq dim
            # minor over model — composite sharding preserved
            return ex.fold_anchor(y)

        def stage_hook(y, axis):
            # re-assert the planned stage layout on intra-block tensors
            return ex.anchor(y, _STAGE_OF_AXIS[axis])

        from repro.models.attention import chunked_attention, AttnConfig
        acfg = AttnConfig(d_model=cfg.d_model, n_heads=cfg.n_heads,
                          n_kv_heads=cfg.kvh, head_dim=cfg.dh, rope=False)

        def attn_impl(q, k, v):
            return chunked_attention(q, k, v, acfg, mesh=mesh,
                                     layout="batch", causal=False,
                                     backend=backend)

    with tracing.scope(tracing.EMBED):
        x = L.patch_embed(params["embed"], x)
        x = add_pos_embed(x, cfg, t_offset, s_offset)
        t_emb = None
        if cfg.modulate and t is not None:
            t_emb = L.linear(params["t_proj"], L.timestep_embedding(
                t, cfg.d_model).astype(x.dtype))
    x = ex.enter(x)                   # planned entry (dataloader split on T)

    layers = params["layers"]
    n = jax.tree_util.tree_leaves(layers)[0].shape[0]

    def layer_params(stack, i):
        # on a mesh the stack is ZeRO-sharded: name where a layer's weights
        # leave it (the partitioner's gathers keep the name of their dot)
        with (tracing.scope(tracing.ZERO) if mesh is not None
              else contextlib.nullcontext()):
            return jax.tree_util.tree_map(lambda a: a[i], stack)

    if isinstance(psched, UnrolledSchedule):
        # non-periodic plan: python-unroll the layer loop; boundaries (and
        # anchors) address stages by ABSOLUTE index so every layer pair may
        # use its own layouts — fwd and planned bwd alike
        def pair_body(xc, lp, i):
            hooks = (None, None)
            if stage_hook is not None:
                hooks = (lambda y, _a: ex.anchor(y, 2 * i),
                         lambda y, _a: ex.anchor(y, 2 * i + 1))
            xc = t2d_block(lp["spatial"], xc, cfg, axis=2, t_emb=t_emb,
                           backend=backend, attn_impl=attn_impl,
                           fold_hook=fold_hook, stage_hook=hooks[0])
            xc = ex.boundary(xc, 2 * i + 1)
            xc = t2d_block(lp["temporal"], xc, cfg, axis=1, t_emb=t_emb,
                           backend=backend, attn_impl=attn_impl,
                           fold_hook=fold_hook, stage_hook=hooks[1])
            if 2 * i + 2 < psched.n_stages:
                xc = ex.boundary(xc, 2 * i + 2)
            return xc

        with tracing.scope(tracing.LAYERS):
            for i in range(n):
                lp = layer_params(layers, i)
                body = (jax.checkpoint(functools.partial(pair_body, i=i),
                                       prevent_cse=False)
                        if remat else functools.partial(pair_body, i=i))
                x = body(x, lp)
    else:
        def layer_body(xc, lp):
            # spatial stage: computes over S — planned shard stays on T
            xc = t2d_block(lp["spatial"], xc, cfg, axis=2, t_emb=t_emb,
                           backend=backend, attn_impl=attn_impl,
                           fold_hook=fold_hook, stage_hook=stage_hook)
            # planned boundary: dynamic switch T -> S (one all-to-all)
            xc = ex.boundary(xc, 1)
            xc = t2d_block(lp["temporal"], xc, cfg, axis=1, t_emb=t_emb,
                           backend=backend, attn_impl=attn_impl,
                           fold_hook=fold_hook, stage_hook=stage_hook)
            # planned wrap-around: dynamic switch S -> T
            xc = ex.wrap(xc)
            return xc, None

        # hierarchical remat: scan over GROUPS of layer pairs so only one
        # residual carry per group is stored (halves activation-carry memory
        # for the long-temporal cells at the cost of one extra in-group
        # recompute)
        g = remat_group if (remat and n % remat_group == 0) else 1

        def group_body(xc, gp):
            for i in range(g):
                xc, _ = layer_body(xc, layer_params(gp, i))
            return xc, None

        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((n // g, g) + a.shape[1:]), layers)
        body = (jax.checkpoint(group_body, prevent_cse=False) if remat
                else group_body)
        from repro.models.flags import scan_or_unroll
        with tracing.scope(tracing.LAYERS):
            x, _ = scan_or_unroll(body, x, grouped)
    x = ex.exit(x)                    # planned final layout (loss/head on T)
    with tracing.scope(tracing.LOSS):
        x = L.rms_norm(params["final_norm"], x)
        return L.linear(params["head"], x)


def t2d_loss(params, batch, cfg: T2DConfig, **kw):
    """Diffusion-style MSE against target latents."""
    pred = forward(params, batch["x"], batch.get("t"), cfg, **kw)
    with tracing.scope(tracing.LOSS):
        err = (pred.astype(jnp.float32) -
               batch["target"].astype(jnp.float32)) ** 2
        return jnp.mean(err), {}


# ---------------------------------------------------------------------------
# Explicit shard_map path (paper-faithful DSP + embedded-SP baselines)
# ---------------------------------------------------------------------------

def make_spmd_forward(cfg: T2DConfig, mesh: Mesh, *, mode: str = "dsp",
                      axis_name: str = "model", backend: str = "ref",
                      remat: bool = False, overlap: Optional[str] = None):
    """Build jit-able forward(params, x, t) where x: (B, T, S, C_in) global.

    mode in {"dsp", "ulysses", "ulysses_fused", "ring", "megatron",
    "hybrid"}.  Sequence parallel over ``axis_name`` (T enters sharded);
    batch over the remaining axes.  Collective counts/volumes match paper
    Table 3.

    mode="hybrid" is USP (the strategy DP's ICI x DCN pick): the mesh must
    carry the 2D SP process grid ("sp_out", "sp_in") from
    ``launch.mesh.make_sp2d_mesh`` — T enters sharded over BOTH axes
    (sp_out major); temporal attention a2as q/k/v inside "sp_in" and
    ring-streams K/V across "sp_out" (``core.ulysses.usp_attention``);
    spatial blocks are fully local.  Requires n_heads and kv_heads
    divisible by the inner size.

    ``overlap`` (dsp mode only) runs every planned switch through
    ``core.overlap.overlapped_switch``: n-1 independent per-shard
    ``ppermute`` hops the compiler interleaves with the consuming block's
    kernels, instead of one blocking all-to-all.
    """
    sp_axes = ("sp_out", "sp_in") if mode == "hybrid" else (axis_name,)
    dp_axes = tuple(a for a in mesh.axis_names if a not in sp_axes)
    dp = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    if mode == "hybrid":
        missing = [a for a in sp_axes if a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"hybrid mode needs a 2D SP mesh with axes {sp_axes} "
                f"(launch.mesh.make_sp2d_mesh); missing {missing}")
        h_out = mesh.shape["sp_out"]
        p_in = mesh.shape["sp_in"]
        n = h_out * p_in
        if cfg.n_heads % p_in or cfg.kvh % p_in:
            raise ValueError(
                f"hybrid mode a2as heads over the inner axis: n_heads "
                f"{cfg.n_heads} and kv_heads {cfg.kvh} must divide by "
                f"sp_in={p_in}")
    else:
        n = mesh.shape[axis_name]
    if mode == "megatron" and cfg.kvh != cfg.n_heads:
        raise ValueError("megatron mode TP-slices wq/wk/wv uniformly and "
                         "assumes MHA (n_kv_heads == n_heads)")
    if mode == "ulysses_fused" and cfg.kvh != cfg.n_heads:
        raise ValueError("ulysses_fused stacks q/k/v and needs equal "
                         "shapes (MHA); use mode='ulysses' for GQA")
    if mode == "ulysses" and cfg.kvh != cfg.n_heads and cfg.kvh % n:
        raise ValueError(
            f"ulysses mode a2as K/V heads over the SP axis: kv_heads "
            f"{cfg.kvh} must divide by n={n} (or use MHA)")

    def local_fwd(params, x, t):
        if mode == "hybrid":
            idx = (jax.lax.axis_index("sp_out") * p_in
                   + jax.lax.axis_index("sp_in"))
        else:
            idx = jax.lax.axis_index(axis_name)
        t_loc = x.shape[1]
        x = L.patch_embed(params["embed"], x)
        x = add_pos_embed(x, cfg, t_offset=idx * t_loc, s_offset=0)
        t_emb = None
        if cfg.modulate and t is not None:
            t_emb = L.linear(params["t_proj"],
                             L.timestep_embedding(t, cfg.d_model).astype(x.dtype))

        if mode == "dsp":
            # the SAME planned schedule as the auto path, explicit backend:
            # transitions are the paper's collectives inside shard_map
            psched = dsp_schedule(cfg, n, t_len=x.shape[1] * n,
                                  s_len=x.shape[2], batch=x.shape[0],
                                  overlap=overlap)
            ex = ScheduleExecutor(psched, backend="explicit",
                                  axis_name=axis_name)

            def body(xc, lp):
                xc = t2d_block(lp["spatial"], xc, cfg, axis=2, t_emb=t_emb,
                               backend=backend)
                xc = ex.boundary(xc, 1)              # planned switch T -> S
                xc = t2d_block(lp["temporal"], xc, cfg, axis=1, t_emb=t_emb,
                               backend=backend)
                xc = ex.wrap(xc)                     # planned switch S -> T
                return xc, None
        elif mode in ("ulysses", "ulysses_fused"):
            ua = (ulysses_core.ulysses_attention if mode == "ulysses"
                  else ulysses_core.ulysses_attention_fused)

            def temporal_attn(q, k, v):
                def inner(qq, kk, vv):
                    return _default_attn(backend)(qq, kk, vv)
                return ua(q, k, v, inner, axis_name=axis_name)

            def body(xc, lp):
                xc = t2d_block(lp["spatial"], xc, cfg, axis=2, t_emb=t_emb,
                               backend=backend)
                xc = t2d_block(lp["temporal"], xc, cfg, axis=1, t_emb=t_emb,
                               attn_impl=temporal_attn, backend=backend)
                return xc, None
        elif mode == "ring":
            def temporal_attn(q, k, v):
                return ring_core.ring_attention(q, k, v, axis_name=axis_name,
                                                causal=False)

            def body(xc, lp):
                xc = t2d_block(lp["spatial"], xc, cfg, axis=2, t_emb=t_emb,
                               backend=backend)
                xc = t2d_block(lp["temporal"], xc, cfg, axis=1, t_emb=t_emb,
                               attn_impl=temporal_attn, backend=backend)
                return xc, None
        elif mode == "hybrid":
            def temporal_attn(q, k, v):
                return ulysses_core.usp_attention(
                    q, k, v, inner_axis="sp_in", outer_axis="sp_out",
                    causal=False)

            def body(xc, lp):
                xc = t2d_block(lp["spatial"], xc, cfg, axis=2, t_emb=t_emb,
                               backend=backend)
                xc = t2d_block(lp["temporal"], xc, cfg, axis=1, t_emb=t_emb,
                               attn_impl=temporal_attn, backend=backend)
                return xc, None
        elif mode == "megatron":
            def body(xc, lp):
                xc = _megatron_block(lp["spatial"], xc, cfg, axis=2,
                                     t_emb=t_emb, axis_name=axis_name,
                                     backend=backend)
                xc = _megatron_block(lp["temporal"], xc, cfg, axis=1,
                                     t_emb=t_emb, axis_name=axis_name,
                                     backend=backend)
                return xc, None
        else:
            raise ValueError(mode)

        b = jax.checkpoint(body, prevent_cse=False) if remat else body
        x, _ = jax.lax.scan(b, x, params["layers"])
        x = L.rms_norm(params["final_norm"], x)
        return L.linear(params["head"], x)

    # T (dim 1) enters sharded: over the joint 2D SP grid in hybrid mode
    # (sp_out MAJOR — each sp_out slice is one host's contiguous T block),
    # over the single SP axis otherwise
    seq_entry = sp_axes if mode == "hybrid" else axis_name
    batch_spec = P(dp, seq_entry, None, None)
    t_spec = P(dp) if dp is not None else P()
    fwd = jax.shard_map(
        local_fwd, mesh=mesh,
        in_specs=(P(), batch_spec, t_spec),
        out_specs=batch_spec,
        check_vma=False)
    return fwd
