"""Decoder-only LM assembled from per-layer block specs.

One module covers the dense / MoE / SSM / hybrid members of the assigned
pool: each layer is (mixer, ffn) where mixer in {attn, ssm} and ffn in
{mlp, moe, none}.  Layers repeat in *periods* (gemma2: local/global pair;
jamba: 8-layer mamba/attn interleave; dense: period 1) and the period stack
is driven by ``jax.lax.scan`` over stacked parameters — compile time and HLO
size stay flat in depth, which matters when dry-running 88-layer models on
512 simulated devices.

Sharding is injected through a ``Sharder`` (repro.parallel): the model calls
semantic layout hooks and never touches the mesh.  In DSP mode the
hook-boundary layout changes are the paper's dynamic switches, and WHICH dim
each stage shards comes from the planned switching schedule
(``stages``/``dsp_schedule`` -> ``core.plan`` solver), attached to the
sharder at the top of each forward.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.plan import Stage
from repro.core.schedule import Schedule, plan_joint_schedule, plan_schedule
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S
from repro.parallel.partition import Sharder, ParallelPlan, make_sharder


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str = "attn"                  # "attn" | "ssm"
    ffn: str = "mlp"                     # "mlp" | "moe" | "none"
    window: Optional[int] = None         # sliding window for this layer


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    # attention variants
    mlp_kind: str = "silu_glu"
    qk_norm: bool = False
    rope_theta: float = 10000.0
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    attn_bias: bool = False
    embed_scale: bool = False
    norm_kind: str = "rms"               # "rms" | "layer"
    post_norm: bool = False              # gemma2-style post-block norms
    tie_embeddings: bool = True
    # layer pattern (period definition)
    window: Optional[int] = None
    window_pattern: Optional[str] = None  # "local_global"
    ssm_every: Optional[int] = None       # jamba: attn at i%ssm_every==offset
    ssm_attn_offset: int = 3
    pure_ssm: bool = False                # mamba2
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1                    # moe at i % moe_every == moe_offset
    moe_offset: int = 0
    n_shared: int = 0
    shared_ff: Optional[int] = None
    dense_ff: Optional[int] = None        # arctic parallel-dense residual
    norm_topk: bool = True
    ep_pad: Optional[int] = None          # pad experts for EP divisibility
    # ssm geometry
    ssm_cfg: Optional[S.SSMConfig] = None
    # frontend stub (vlm): precomputed patch embeddings merged into sequence
    frontend_dim: Optional[int] = None
    frontend_tokens: int = 0
    dtype: Any = jnp.bfloat16
    # KV cache dtype (None = dtype).  100B+ archs serve fp8 KV: mistral's
    # 128-request x 32k x 88-layer cache is 4.7 TB in bf16 — quantised
    # serving is the production norm, not an optimisation
    cache_dtype: Any = None

    # -- derived -------------------------------------------------------------
    def attn_cfg(self, window: Optional[int]) -> A.AttnConfig:
        return A.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            qk_norm=self.qk_norm, rope=True, rope_theta=self.rope_theta,
            window=window, softcap=self.attn_softcap, bias=self.attn_bias)

    def period_specs(self) -> List[LayerSpec]:
        if self.pure_ssm:
            return [LayerSpec(mixer="ssm", ffn="none")]
        if self.ssm_every:                              # hybrid (jamba)
            out = []
            for i in range(self.ssm_every):
                mixer = "attn" if i == self.ssm_attn_offset else "ssm"
                ffn = ("moe" if self.n_experts and
                       i % self.moe_every == self.moe_offset else "mlp")
                out.append(LayerSpec(mixer=mixer, ffn=ffn, window=None))
            return out
        if self.window_pattern == "local_global":
            return [LayerSpec(ffn=self._ffn(0), window=self.window),
                    LayerSpec(ffn=self._ffn(1), window=None)]
        if self.n_experts and self.moe_every > 1:
            return [LayerSpec(ffn=self._ffn(i), window=self.window)
                    for i in range(self.moe_every)]
        return [LayerSpec(ffn=self._ffn(0), window=self.window)]

    def _ffn(self, i: int) -> str:
        if self.n_experts and i % self.moe_every == self.moe_offset:
            return "moe"
        return "mlp"

    @property
    def n_periods(self) -> int:
        period = len(self.period_specs())
        assert self.n_layers % period == 0, (self.n_layers, period)
        return self.n_layers // period


# ---------------------------------------------------------------------------
# DSP stage declaration + planned switching schedule
# ---------------------------------------------------------------------------

def stages(cfg: LMConfig, *, seq: Optional[int] = None,
           batch: Optional[int] = None,
           grad_dtype_bytes: Optional[int] = None) -> List[Stage]:
    """Declare the model's stage sequence on the logical (B, S, H·Dh) view
    for the switching planner: channel-wise stages (projections, norms, FFN,
    MoE) compute along dim 2, the mixer cores (attention softmax / SSD scan)
    along dim 1 — DSP-1D, where the "second sequence dim" is the head or
    channel axis.  With extents given, stages carry global shapes so the
    planner prices transitions in bytes; ``grad_dtype_bytes`` declares the
    width of the gradients crossing the same boundaries backward (joint
    fwd+bwd planning; defaults to the activation dtype)."""
    specs = cfg.period_specs()
    shape = (batch, seq, cfg.d_model) if None not in (seq, batch) else None
    db = jnp.dtype(cfg.dtype).itemsize
    gb = grad_dtype_bytes
    out: List[Stage] = []
    for layer in range(cfg.n_layers):
        spec = specs[layer % len(specs)]
        # per-period grad declaration: the cotangent crossing each boundary
        # backward is activation-shaped (Stage.bwd_shape defaults to shape)
        # at grad width ``gb`` — the joint round-trip DP prices the backward
        # leg from these
        out.append(Stage(frozenset({2}), f"L{layer}.proj", shape, db,
                         bwd_dtype_bytes=gb))
        out.append(Stage(frozenset({1}), f"L{layer}.{spec.mixer}", shape, db,
                         bwd_dtype_bytes=gb))
        if spec.ffn != "none":
            out.append(Stage(frozenset({2}), f"L{layer}.{spec.ffn}", shape,
                             db, bwd_dtype_bytes=gb))
    return out


def stage_period(cfg: LMConfig) -> int:
    """Stages per scanned layer period."""
    return sum(2 if s.ffn == "none" else 3 for s in cfg.period_specs())


def dsp_schedule(cfg: LMConfig, n: int, *, seq: Optional[int] = None,
                 batch: Optional[int] = None, topology=None,
                 joint: bool = False,
                 grad_dtype_bytes: Optional[int] = None,
                 bwd_dims=None, overlap: Optional[str] = None) -> Schedule:
    """Solve the switching plan (enter sequence-sharded from the dataloader
    split, return to it for the loss) and validate it is scan-periodic.
    ``topology`` prices the plan in seconds on the mesh's links (byte model
    when None); ``joint=True`` plans the backward pass too
    (``core.plan.plan_joint``) — and since the scanned execution consumes
    non-mirrored plans (per-period custom_vjp boundaries through the
    Sharder hooks; docs/architecture.md §3.5), the joint DP runs for real:
    the priced round trip IS the executed round trip.  Only a joint plan
    that is not scan-periodic falls back to the mirrored forward-optimal
    baseline (``lax.scan`` needs a steady state on both legs).

    ``bwd_dims`` forces a specific backward plan (a per-period pattern or
    the full per-stage tuple) — the parity/HLO test tier and benchmarks use
    it to pin non-mirrored execution on instances where the DP keeps the
    mirror.  Forcing deliberately skips the planner's ``Stage.allows``
    feasibility check: this stage graph admits exactly one dim per stage,
    so every non-mirrored plan is "infeasible" in the cost model's sense —
    gradients stay bit-identical regardless (the constraints are layout
    only), but the executed collectives of a forced plan may exceed what
    the pricing assumes (XLA inserts the intra-stage reshards the cost
    model would have charged a feasible plan nothing for).

    ``overlap`` attaches roofline compute estimates to the stages, prices
    switches at their exposed seconds, and stamps the mode on the schedule
    (the explicit executor then streams each switch as per-shard
    ``ppermute`` hops; docs/architecture.md §3.6)."""
    st = stages(cfg, seq=seq, batch=batch, grad_dtype_bytes=grad_dtype_bytes)
    if overlap is not None:
        from repro.analysis.roofline import attach_compute_seconds
        st = attach_compute_seconds(
            st, cfg, topology if topology is not None else max(n, 1))
    period = stage_period(cfg)
    if joint:
        sched = plan_joint_schedule(st, (1, 2), n=max(n, 1), initial=1,
                                    final=1, topology=topology,
                                    overlap=overlap)
        try:
            sched.periodic(period)
        except ValueError:
            sched = plan_joint_schedule(st, (1, 2), n=max(n, 1), initial=1,
                                        final=1, topology=topology,
                                        require_mirrored=True,
                                        overlap=overlap)
    else:
        sched = plan_schedule(st, (1, 2), n=max(n, 1), initial=1, final=1,
                              topology=topology, overlap=overlap)
    if bwd_dims is not None:
        bwd_dims = tuple(bwd_dims)
        if len(bwd_dims) == period:
            bwd_dims = bwd_dims * (len(st) // period)
        if len(bwd_dims) != len(st):
            raise ValueError(
                f"bwd_dims must cover one period ({period} stages) or the "
                f"full plan ({len(st)} stages); got {len(bwd_dims)}")
        sched = dataclasses.replace(sched, bwd_dims=bwd_dims)
    sched.periodic(period)     # scanned layers: steady state, both legs
    return sched


def _with_planned_schedule(sharder: Sharder, cfg: LMConfig,
                           seq: Optional[int] = None,
                           batch: Optional[int] = None) -> Sharder:
    """Attach the planned schedule when running DSP with a mesh and none was
    provided — the plan, not the model, decides the stage layouts, priced on
    the sharder's topology when it carries one."""
    if (sharder.mesh is None or sharder.plan.mode != "dsp"
            or sharder.schedule is not None):
        return sharder
    return sharder.with_schedule(
        dsp_schedule(cfg, sharder.sp_size, seq=seq, batch=batch,
                     topology=sharder.topology))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_norm_kind(cfg: LMConfig, d: int):
    return L.init_norm(d, bias=(cfg.norm_kind == "layer"), dtype=cfg.dtype)


def _apply_norm(cfg: LMConfig, p, x):
    if cfg.norm_kind == "layer":
        return L.layer_norm(p, x)
    return L.rms_norm(p, x)


def _init_layer(key, cfg: LMConfig, spec: LayerSpec):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": _init_norm_kind(cfg, cfg.d_model)}
    if spec.mixer == "attn":
        p["attn"] = A.init_attention(ks[0], cfg.attn_cfg(spec.window),
                                     dtype=cfg.dtype)
    else:
        p["ssm"] = S.init_ssm(ks[0], cfg.ssm_cfg, dtype=cfg.dtype)
    if cfg.post_norm:
        p["pn1"] = _init_norm_kind(cfg, cfg.d_model)
    if spec.ffn != "none":
        p["ln2"] = _init_norm_kind(cfg, cfg.d_model)
        if spec.ffn == "moe":
            p["moe"] = M.init_moe(
                ks[1], cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.top_k,
                n_shared=cfg.n_shared, shared_ff=cfg.shared_ff,
                dense_ff=cfg.dense_ff, kind=cfg.mlp_kind,
                pad_experts_to=cfg.ep_pad, dtype=cfg.dtype)
        else:
            ff = cfg.d_ff if not cfg.n_experts else (
                cfg.dense_ff or cfg.d_ff)
            p["mlp"] = L.init_mlp(ks[2], cfg.d_model, ff, kind=cfg.mlp_kind,
                                  dtype=cfg.dtype)
        if cfg.post_norm:
            p["pn2"] = _init_norm_kind(cfg, cfg.d_model)
    return p


def init_lm(key, cfg: LMConfig):
    """Returns the parameter tree.  Per-period layer params live under
    ``periods`` with a stacked leading dim of n_periods (scanned)."""
    specs = cfg.period_specs()
    kemb, kper, kfin, kfront, kunemb = jax.random.split(key, 5)

    def one_period(k):
        pk = jax.random.split(k, len(specs))
        return {str(i): _init_layer(pk[i], cfg, spec)
                for i, spec in enumerate(specs)}

    period_keys = jax.random.split(kper, cfg.n_periods)
    periods = jax.vmap(one_period)(period_keys)

    params: Dict[str, Any] = {
        "embed": L.init_embedding(kemb, cfg.vocab, cfg.d_model,
                                  dtype=cfg.dtype),
        "periods": periods,
        "final_norm": _init_norm_kind(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_embedding(kunemb, cfg.vocab, cfg.d_model,
                                             dtype=cfg.dtype)
    if cfg.frontend_dim:
        params["frontend"] = L.init_patch_embed(kfront, cfg.frontend_dim,
                                                cfg.d_model, dtype=cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

def _attn_with_switch(p, x, cfg: LMConfig, spec: LayerSpec, sharder: Sharder,
                      backend: str, fused_switch: bool):
    return A.attention_sp(p["attn"], x, cfg.attn_cfg(spec.window),
                          sharder=sharder, backend=backend,
                          fused_switch=fused_switch, causal=True)


def moe_meta(cfg: LMConfig) -> M.MoEArgs:
    return M.MoEArgs(n_experts=cfg.n_experts, top_k=cfg.top_k,
                     e_phys=cfg.ep_pad or cfg.n_experts, kind=cfg.mlp_kind,
                     has_shared=cfg.n_shared > 0,
                     has_dense=cfg.dense_ff is not None)


def _apply_layer(p, x, cfg: LMConfig, spec: LayerSpec, sharder: Sharder,
                 backend: str, fused_switch: bool, moe_impl: str):
    aux = jnp.zeros((), jnp.float32)
    h = _apply_norm(cfg, p["ln1"], x)
    if spec.mixer == "attn":
        h = _attn_with_switch(p, h, cfg, spec, sharder, backend, fused_switch)
    else:
        h = S.ssm_block(p["ssm"], h, cfg.ssm_cfg, backend=backend,
                        sharder=sharder)
        h = sharder.mixer_exit3(h)
    if cfg.post_norm:
        h = _apply_norm(cfg, p["pn1"], h)
    x = x + h
    if spec.ffn != "none":
        h = _apply_norm(cfg, p["ln2"], x)
        if spec.ffn == "moe":
            h, moe_aux = M.moe(p["moe"], h, moe_meta(cfg), impl=moe_impl,
                               norm_topk=cfg.norm_topk,
                               expert_hook=sharder.moe_experts)
            aux = aux + moe_aux["load_balance"]
        else:
            h = L.mlp(p["mlp"], h, cfg.mlp_kind)
        h = sharder.act3(h)
        if cfg.post_norm:
            h = _apply_norm(cfg, p["pn2"], h)
        x = x + h
        # layer exit: a resid-stage boundary (the ffn was the last stage)
        return sharder.act3(x), aux
    # ffn-less layers end on the mixer stage: the boundary's backward
    # carries the cotangent into the mixer's planned bwd layout
    return sharder.mixer_exit3(x), aux


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def sharded_embed(params, tokens, cfg: LMConfig, sharder: Sharder):
    """Vocab-parallel embedding with a table RING.

    The table is vocab-sharded over the model axis; tokens are
    sequence-sharded over the SAME axis, so a Megatron-style masked-psum
    would mix different sequence chunks.  Instead each device accumulates
    its own sequence chunk while the table chunks rotate around the ring
    (collective-permute x (N-1)): communication = table bytes, independent
    of sequence length, and no reduction at all.

    Falls back to a plain gather when no mesh / vocab not divisible.
    """
    table = params["embed"]["table"]
    vocab, d = table.shape
    mesh = sharder.mesh
    sp = mesh.shape.get("model", 1) if mesh is not None else 1
    if (mesh is None or sp == 1 or vocab % sp or
            not sharder.plan.shard_vocab):
        return L.embed(params["embed"], tokens,
                       scale_by_sqrt_dim=cfg.embed_scale)
    from jax.sharding import PartitionSpec as P
    dp_size = 1
    for a in sharder.dp:
        dp_size *= mesh.shape.get(a, 1)
    dp = sharder.dp if len(sharder.dp) > 1 else sharder.dp[0]
    if tokens.shape[0] % dp_size:
        dp = None                      # batch=1 decode: replicate batch
    seq_shard = tokens.shape[1] % sp == 0 and tokens.shape[1] > 1
    chunk = vocab // sp

    def local(tbl, tok):
        from repro.core.overlap import ring_stream

        def fold(i, src, tbl_c, acc):
            # ``src`` owns the held table chunk: gather the tokens that
            # fall in its vocab range, mask the rest
            rel = tok - src * chunk
            ok = (rel >= 0) & (rel < chunk)
            e = jnp.take(tbl_c, jnp.clip(rel, 0, chunk - 1), axis=0)
            return acc + jnp.where(ok[..., None], e, 0)

        acc0 = jnp.zeros(tok.shape + (d,), tbl.dtype)
        acc0 = jax.lax.pcast(acc0, ("model",), to="varying")
        return ring_stream(tbl, acc0, fold, axis_name="model")

    tok_spec = P(dp, "model") if seq_shard else P(dp, None)
    out_spec = P(dp, "model", None) if seq_shard else P(dp, None, None)
    fn = jax.shard_map(local, mesh=mesh,
                          in_specs=(P("model", None), tok_spec),
                          out_specs=out_spec, check_vma=False)
    x = fn(table, tokens)
    if cfg.embed_scale:
        x = x * math.sqrt(d)
    return x.astype(table.dtype)


REMAT_POLICIES = {
    "full": None,                       # recompute everything (default)
    "dots": "dots_with_no_batch_dims_saveable",   # keep matmul outputs
    "none": "everything_saveable",
}


def _remat(body, policy: str):
    if policy == "none":
        return body
    kw = {}
    name = REMAT_POLICIES.get(policy)
    if name:
        kw["policy"] = getattr(jax.checkpoint_policies, name)
    return jax.checkpoint(body, prevent_cse=False, **kw)


def forward(params, tokens, cfg: LMConfig, *, sharder: Optional[Sharder] = None,
            backend: str = "pallas", remat: bool = True,
            remat_policy: str = "full",
            fused_switch: bool = True, moe_impl: str = "gather",
            extra: Optional[dict] = None):
    """tokens: (B, S) int32 -> final hidden states (B, S, C) and aux scalars.

    ``extra['patch_embeds']`` (B, frontend_tokens, frontend_dim) replaces the
    first ``frontend_tokens`` embedding positions (VLM stub frontend).
    """
    sharder = sharder or make_sharder(None, ParallelPlan(mode="none"))
    sharder = _with_planned_schedule(sharder, cfg, seq=tokens.shape[1],
                                     batch=tokens.shape[0])
    specs = cfg.period_specs()
    x = sharded_embed(params, tokens, cfg, sharder)
    if cfg.frontend_dim and extra and "patch_embeds" in extra:
        pe = L.patch_embed(params["frontend"], extra["patch_embeds"])
        x = jnp.concatenate([pe.astype(x.dtype),
                             x[:, cfg.frontend_tokens:]], axis=1)
    x = sharder.enter3(x)       # entry boundary; its bwd is the input grad

    def period_body(carry, pp):
        x, aux = carry
        # scan-carry anchor: pins the steady-state backward layout of the
        # cotangent crossing periods (a forward keep — lowers to nothing)
        x = sharder.wrap3(x)
        for i, spec in enumerate(specs):
            x, a = _apply_layer(pp[str(i)], x, cfg, spec, sharder, backend,
                                fused_switch, moe_impl)
            aux = aux + a
        return (x, aux), None

    body = period_body
    if remat:
        body = _remat(period_body, remat_policy)
    from repro.models.flags import scan_or_unroll
    (x, aux), _ = scan_or_unroll(body, (x, jnp.zeros((), jnp.float32)),
                                 params["periods"])
    x = _apply_norm(cfg, params["final_norm"], x)
    return x, {"moe_load_balance": aux}


def logits_fn(params, x, cfg: LMConfig,
              sharder: Optional[Sharder] = None):
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["unembed"]["table"])
    logits = jnp.einsum("bsd,vd->bsv", x, table)
    logits = L.softcap_logits(logits, cfg.final_softcap)
    if sharder is not None:
        logits = sharder.logits(logits)
    return logits


def chunked_xent(x, table, labels, cfg: LMConfig, *, chunk: int = 512,
                 sharder: Optional[Sharder] = None):
    """Cross-entropy without materialising (B, S, V): scan over S chunks,
    recomputing chunk logits in the backward (checkpoint).  The chunk count
    must be a multiple of the SP degree so the (n, chunk) reshape of the
    sequence-sharded x keeps its sharding (n major)."""
    from repro.models import flags
    b, s, d = x.shape
    sp = 1
    if sharder is not None and sharder.mesh is not None:
        sp = sharder.mesh.shape.get("model", 1)
    chunk = min(chunk, max(s // max(sp, 1), 1))
    while s % chunk:
        chunk //= 2
    if flags.FLAT_COST_MODE:
        chunk = s                    # straight-line (cost compiles only)
    n = s // chunk

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one(xc, lc):
        logits = jnp.einsum("bsd,vd->bsv", xc, table).astype(jnp.float32)
        logits = L.softcap_logits(logits, cfg.final_softcap)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(lz - gold)

    def body(acc, inp):
        xc, lc = inp
        return acc + one(xc, lc), None

    xs = x.reshape(b, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(b, n, chunk).swapaxes(0, 1)
    if sharder is not None:
        xs = sharder.xent_chunks(xs)
        ls = sharder.xent_chunks(ls)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (b * s)


def lm_loss(params, batch, cfg: LMConfig, *, sharder=None, backend="pallas",
            remat=True, remat_policy="full", fused_switch=True,
            moe_impl="gather", aux_weight: float = 0.01):
    x, aux = forward(params, batch["tokens"], cfg, sharder=sharder,
                     backend=backend, remat=remat, remat_policy=remat_policy,
                     fused_switch=fused_switch,
                     moe_impl=moe_impl, extra=batch.get("extra"))
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["unembed"]["table"])
    loss = chunked_xent(x, table, batch["labels"], cfg, sharder=sharder)
    total = loss + aux_weight * aux["moe_load_balance"] / max(cfg.n_layers, 1)
    return total, {"xent": loss, **aux}


# ---------------------------------------------------------------------------
# Parameter counting (roofline MODEL_FLOPS = 6 * N_active * D)
# ---------------------------------------------------------------------------

def param_counts(cfg: LMConfig) -> Dict[str, int]:
    """Returns total and active (per-token) parameter counts."""
    d, dh = cfg.d_model, cfg.head_dim
    total = active = cfg.vocab * d * (1 if cfg.tie_embeddings else 2)
    for spec in cfg.period_specs() * cfg.n_periods:
        if spec.mixer == "attn":
            n = d * dh * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
            total += n; active += n
        else:
            sc = cfg.ssm_cfg
            n_in = d * (2 * sc.d_inner + 2 * sc.n_groups * sc.d_state +
                        sc.n_heads)
            n = n_in + sc.d_inner * d + sc.d_conv * (
                sc.d_inner + 2 * sc.n_groups * sc.d_state)
            total += n; active += n
        if spec.ffn == "mlp":
            ff = cfg.d_ff if not cfg.n_experts else (cfg.dense_ff or cfg.d_ff)
            n = L.mlp_param_count(d, ff, cfg.mlp_kind)
            total += n; active += n
        elif spec.ffn == "moe":
            per = L.mlp_param_count(d, cfg.d_ff, cfg.mlp_kind)
            total += cfg.n_experts * per
            active += cfg.top_k * per
            if cfg.n_shared:
                n = L.mlp_param_count(d, cfg.shared_ff or cfg.n_shared * cfg.d_ff,
                                      cfg.mlp_kind)
                total += n; active += n
            if cfg.dense_ff:
                n = L.mlp_param_count(d, cfg.dense_ff, cfg.mlp_kind)
                total += n; active += n
    return {"total": total, "active": active}


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode (the decode_* / long_* cells)
# ---------------------------------------------------------------------------

def init_caches(cfg: LMConfig, batch: int, max_len: int, *,
                dtype=None, per_slot_pos: bool = False):
    """Concrete zero caches, stacked per period (scan layout).  Attention
    layers carry {k, v} of (B, Hkv, max_len, Dh); SSM layers carry
    {conv, state}.  ``pos`` is the write position: one shared scalar for a
    static batch, or a (B,) vector with ``per_slot_pos`` (continuous
    batching: every slot appends and masks at its own length)."""
    kv_dtype = dtype or cfg.cache_dtype or cfg.dtype
    ssm_dtype = dtype or cfg.dtype        # conv/state stay wide (tiny, and
    specs = cfg.period_specs()            # fp8 breaks the conv concat)

    def one_layer(spec: LayerSpec):
        if spec.mixer == "attn":
            shape = (batch, cfg.n_kv_heads, max_len, cfg.head_dim)
            return {"kv": {"k": jnp.zeros(shape, kv_dtype),
                           "v": jnp.zeros(shape, kv_dtype)}}
        sc = cfg.ssm_cfg
        d_xbc = sc.d_inner + 2 * sc.n_groups * sc.d_state
        return {"ssm": {"conv": jnp.zeros((batch, sc.d_conv - 1, d_xbc),
                                          ssm_dtype),
                        "state": jnp.zeros((batch, sc.n_heads, sc.head_dim,
                                            sc.d_state), jnp.float32)}}

    period = {str(i): one_layer(s) for i, s in enumerate(specs)}
    stacked = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape), period)
    pos = (jnp.zeros((batch,), jnp.int32) if per_slot_pos
           else jnp.zeros((), jnp.int32))
    return {"pos": pos, "periods": stacked}


def _decode_layer(p, x, pc, cfg: LMConfig, spec: LayerSpec, pos,
                  sharder: Sharder, backend: str, table=None):
    """One layer of incremental decode.  x: (B, S, C) — S is 1 for the
    decode step, or a prefill-chunk length (the paged scheduler feeds
    prompt slices through this same cell).  ``table`` (B, blocks_per_slot)
    switches attention to the paged block-pool cache layout."""
    aux = None
    h = _apply_norm(cfg, p["ln1"], x)
    if spec.mixer == "attn":
        cache = {"k": pc["kv"]["k"], "v": pc["kv"]["v"], "pos": pos}
        if table is not None:
            cache["table"] = table
        h, new_kv = A.attention(p["attn"], h, cfg.attn_cfg(spec.window),
                                causal=True, cache=cache, sharder=sharder,
                                backend=backend)
        new_pc = {"kv": {"k": sharder.kv_cache(new_kv["k"]),
                         "v": sharder.kv_cache(new_kv["v"])}}
    else:
        h, new_ssm = S.ssm_decode_step(p["ssm"], h, cfg.ssm_cfg, pc["ssm"])
        new_pc = {"ssm": new_ssm}
    if cfg.post_norm:
        h = _apply_norm(cfg, p["pn1"], h)
    x = x + h
    if spec.ffn != "none":
        h = _apply_norm(cfg, p["ln2"], x)
        if spec.ffn == "moe":
            h, _ = M.moe(p["moe"], h, moe_meta(cfg), impl="gather",
                         norm_topk=cfg.norm_topk,
                         expert_hook=sharder.moe_experts)
        else:
            h = L.mlp(p["mlp"], h, cfg.mlp_kind)
        if cfg.post_norm:
            h = _apply_norm(cfg, p["pn2"], h)
        x = x + h
    return x, new_pc


def forward_decode(params, tokens, caches, cfg: LMConfig, *,
                   sharder: Optional[Sharder] = None, backend: str = "ref"):
    """tokens: (B, S) -> (logits (B, S, V), new caches).  The KV caches stay
    *sequence-sharded* over the model axis (DSP decode): the softmax over the
    sharded KV length lowers to small psum collectives.  ``caches['pos']``
    may be a scalar (static batch) or a (B,) per-slot vector (continuous
    batching): each row then appends and masks at its own offset.  S is 1
    on the decode hot path; the paged scheduler also pushes prefill CHUNKS
    (S > 1) through here.  A ``caches['table']`` entry switches to the
    paged block-pool layout (see ``serving.block_pool``): rows write and
    read through their block table instead of a contiguous slot row."""
    sharder = sharder or make_sharder(None, ParallelPlan(mode="none"))
    specs = cfg.period_specs()
    pos = caches["pos"]
    table = caches.get("table")
    x = sharded_embed(params, tokens, cfg, sharder)

    def body(x, inp):
        pp, pc = inp
        new_pc = {}
        for i, spec in enumerate(specs):
            x, new_pc[str(i)] = _decode_layer(pp[str(i)], x, pc[str(i)], cfg,
                                              spec, pos, sharder, backend,
                                              table=table)
        return x, new_pc

    from repro.models.flags import scan_or_unroll
    x, new_periods = scan_or_unroll(body, x, (params["periods"],
                                              caches["periods"]))
    x = _apply_norm(cfg, params["final_norm"], x)
    logits = logits_fn(params, x, cfg, sharder)
    new = {"pos": pos + tokens.shape[1], "periods": new_periods}
    if table is not None:
        new["table"] = table
    return logits, new


def forward_prefill(params, tokens, cfg: LMConfig, *,
                    sharder: Optional[Sharder] = None, backend: str = "ref",
                    fused_switch: bool = True, remat: bool = True,
                    extra: Optional[dict] = None):
    """Full-sequence prefill: returns (last-position logits, caches with
    pos = S).  Cache length == prompt length (the decode cells then append)."""
    sharder = sharder or make_sharder(None, ParallelPlan(mode="none"))
    sharder = _with_planned_schedule(sharder, cfg, seq=tokens.shape[1],
                                     batch=tokens.shape[0])
    specs = cfg.period_specs()
    x = sharded_embed(params, tokens, cfg, sharder)
    if cfg.frontend_dim and extra and "patch_embeds" in extra:
        pe = L.patch_embed(params["frontend"], extra["patch_embeds"])
        x = jnp.concatenate([pe.astype(x.dtype),
                             x[:, cfg.frontend_tokens:]], axis=1)
    x = sharder.act3(x)

    def layer_prefill(p, x, spec):
        h = _apply_norm(cfg, p["ln1"], x)
        if spec.mixer == "attn":
            h, (ck, cv) = A.attention_sp(
                p["attn"], h, cfg.attn_cfg(spec.window), sharder=sharder,
                backend=backend, fused_switch=fused_switch, causal=True,
                return_kv=True)
            pc = {"kv": {"k": sharder.kv_cache(ck),
                         "v": sharder.kv_cache(cv)}}
        else:
            h, ssm_cache = S.ssm_block(
                p["ssm"], h, cfg.ssm_cfg, backend=backend,
                sharder=sharder, return_cache=True)
            h = sharder.act3(h)
            pc = {"ssm": ssm_cache}
        if cfg.post_norm:
            h = _apply_norm(cfg, p["pn1"], h)
        x = x + h
        if spec.ffn != "none":
            h = _apply_norm(cfg, p["ln2"], x)
            if spec.ffn == "moe":
                h, _ = M.moe(p["moe"], h, moe_meta(cfg),
                             norm_topk=cfg.norm_topk,
                             expert_hook=sharder.moe_experts)
            else:
                h = L.mlp(p["mlp"], h, cfg.mlp_kind)
            h = sharder.act3(h)
            if cfg.post_norm:
                h = _apply_norm(cfg, p["pn2"], h)
            x = x + h
        return sharder.act3(x), pc

    def body(x, pp):
        pcs = {}
        for i, spec in enumerate(specs):
            x, pcs[str(i)] = layer_prefill(pp[str(i)], x, spec)
        return x, pcs

    b = jax.checkpoint(body, prevent_cse=False) if remat else body
    from repro.models.flags import scan_or_unroll
    x, periods = scan_or_unroll(b, x, params["periods"])
    x = _apply_norm(cfg, params["final_norm"], x)
    logits = logits_fn(params, x[:, -1:], cfg, sharder)
    return logits, {"pos": jnp.asarray(tokens.shape[1], jnp.int32),
                    "periods": periods}
