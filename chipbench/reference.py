"""Plain float32 reference of the 2D DiT's training step.

Written from the model's equations, independent of the program: patch
embedding plus sinusoidal positions over T and S; a timestep embedding
through one linear; then blocks that alternate attention over S (spatial)
and over T (temporal), each

    h = rms(x) * (1 + scale1) + shift1;  x += gate1 * attn(h) @ wo
    h = rms(x) * (1 + scale2) + shift2;  x += gate2 * gelu_tanh(h @ wi) @ wo2

with (shift1, scale1, gate1, shift2, scale2, gate2) = silu(t_emb) @ w_mod
+ b_mod; then rms norm, a linear head, and the mean squared error against
the target.  AdamW with global-norm clipping follows ``Optimizer``.

The weights enter every product as the configuration stores them: the
float32 master, rounded to the weights' dtype (bf16), with the gradient
taken at the rounded values and applied to the master.  The rounding is
done in integer arithmetic (``round_to``): the TPU compiler may drop a
float32 -> bfloat16 -> float32 convert pair as excess precision, and after
one AdamW step the master is no longer a bfloat16 value.

It runs block by block: the forward keeps each block's input, and the
backward recomputes one block at a time.  On several devices the state and
each block's work are split over them (``Reference``), so that a model
whose float32 state exceeds one chip fits.  Attention is computed in
chunks of sequences small enough for a chunk's scores to stay near 256 MB
on each device.

``precision="fp8"`` is the control: every matrix product, forward and
backward, takes its operands rounded to float8 (e4m3, one scale per
operand) instead of float32 at full precision.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench.data import init_leaf, init_params, leaf_paths

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
CHUNK_BYTES = 1 << 28
NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Model:
    n_layers: int
    d_model: int
    n_heads: int
    head_dim: int
    d_ff: int
    in_dim: int

    @classmethod
    def from_config(cls, config: dict) -> "Model":
        return cls(**{f.name: config[f.name]
                      for f in dataclasses.fields(cls)})


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """AdamW: linear warm-up to ``peak_lr`` over ``warmup_steps``, then a
    cosine to ``min_lr_ratio * peak_lr`` at ``total_steps``; gradients
    clipped to global norm ``grad_clip``; decoupled weight decay on every
    weight."""
    peak_lr: float
    warmup_steps: int
    total_steps: int
    min_lr_ratio: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float

    def lr(self, step: int) -> float:
        if step < self.warmup_steps:
            return self.peak_lr * step / max(self.warmup_steps, 1)
        frac = min(max((step - self.warmup_steps)
                       / max(self.total_steps - self.warmup_steps, 1), 0.0),
                   1.0)
        return self.peak_lr * (self.min_lr_ratio + (1 - self.min_lr_ratio)
                               * 0.5 * (1 + math.cos(math.pi * frac)))


# ---------------------------------------------------------------------------
# The model, in float32
# ---------------------------------------------------------------------------

def _q8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / s).astype(FP8).astype(jnp.float32) * s


def _ein32(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein8(spec, a, b):
    return _ein32(spec, _q8(a), _q8(b))


def _ein8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return _ein32(spec, qa, qb), (qa, qb)


def _ein8_bwd(spec, res, g):
    _, back = jax.vjp(functools.partial(_ein32, spec), *res)
    return back(_q8(g))


_ein8.defvjp(_ein8_fwd, _ein8_bwd)


def _ein(spec, a, b, precision):
    return _ein8(spec, a, b) if precision == "fp8" else _ein32(spec, a, b)


def _linear(p, x, precision):
    y = _ein("...i,io->...o", x, p["w"], precision)
    return y + p["b"] if "b" in p else y


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _freqs(d):
    half = d // 2
    return jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                   / half)


def _sincos(n, d):
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * _freqs(d)[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def embed(p, x, precision):
    """Patched latents (B, T, S, in) -> (B, T, S, C) plus positions."""
    _, t, s, _ = x.shape
    y = _linear(p["proj"], x, precision)
    c = y.shape[-1]
    return y + _sincos(t, c)[None, :, None] + _sincos(s, c)[None, None]


def timestep(p, t, d, precision):
    ang = t[:, None] * _freqs(d)[None]
    return _linear(p, jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1),
                   precision)


def _chunk(n, per_row):
    c = n
    while c > 1 and c * per_row > CHUNK_BYTES:
        c -= 1
        while n % c:
            c -= 1
    return c


def attention(q, k, v, precision, shards=1):
    """softmax(q k^T / sqrt(dh)) v over L; q, k, v: (N, L, H, dh), whose N
    rows may be split over ``shards`` devices.  Chunk j holds the rows
    j, j + chunks, ..., so that every device keeps its share of each."""
    n, l, h, dh = q.shape

    def one(qkv):
        qc, kc, vc = qkv
        s = _ein("nqhd,nkhd->nhqk", qc, kc, precision) * dh ** -0.5
        return _ein("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), vc, precision)

    c = _chunk(n, h * l * l * 4 / shards)
    if c == n:
        return one((q, k, v))
    split = lambda a: a.reshape((c, n // c) + a.shape[1:]).swapaxes(0, 1)
    o = jax.lax.map(jax.checkpoint(one), (split(q), split(k), split(v)))
    return o.swapaxes(0, 1).reshape(q.shape)


def block(p, x, t_emb, *, axis: int, m: Model, precision: str, shards=1):
    """One block attending along ``axis`` (1 = T, 2 = S) of x (B, T, S, C)."""
    b, t, s, c = x.shape
    mod = _linear(p["mod"]["proj"], jax.nn.silu(t_emb), precision)
    sh1, sc1, g1, sh2, sc2, g2 = (u[:, None, None, :]
                                  for u in jnp.split(mod, 6, -1))
    h = _rms(x, p["ln1"]["scale"]) * (1 + sc1) + sh1
    if axis == 2:
        hf = h.reshape(b * t, s, c)
    else:
        hf = h.transpose(0, 2, 1, 3).reshape(b * s, t, c)
    n, l, _ = hf.shape
    heads = lambda w: _linear(w, hf, precision).reshape(
        n, l, m.n_heads, m.head_dim)
    o = attention(heads(p["wq"]), heads(p["wk"]), heads(p["wv"]), precision,
                  shards)
    o = _linear(p["wo"], o.reshape(n, l, m.n_heads * m.head_dim), precision)
    if axis == 2:
        o = o.reshape(b, t, s, c)
    else:
        o = o.reshape(b, s, t, c).transpose(0, 2, 1, 3)
    x = x + g1 * o
    h = _rms(x, p["ln2"]["scale"]) * (1 + sc2) + sh2
    h = _linear(p["mlp"]["wo"],
                _gelu_tanh(_linear(p["mlp"]["wi"], h, precision)), precision)
    return x + g2 * h


def head_loss(p, x, target, precision):
    pred = _linear(p["head"], _rms(x, p["final_norm"]["scale"]), precision)
    return jnp.mean((pred - target) ** 2)


# ---------------------------------------------------------------------------
# Training, block by block
# ---------------------------------------------------------------------------

def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def round_to(a, dtype: str):
    """Float32 ``a`` rounded to nearest even in ``dtype`` (a name), as
    float32, by integer arithmetic that no compiler may skip."""
    if dtype == "float32":
        return a
    if dtype != "bfloat16":
        raise ValueError(f"no rounding to {dtype}")
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + (np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1)))
    return jax.lax.bitcast_convert_type(bits & np.uint32(0xFFFF0000),
                                        jnp.float32)


def _stored(tree, dtypes):
    """``tree``'s values rounded to ``dtypes`` (a matching tree of dtype
    names), with the gradient passed straight to the float32 values."""
    return jax.tree_util.tree_map(
        lambda a, d: a + jax.lax.stop_gradient(round_to(a, d) - a),
        tree, dtypes)


def _sq(tree):
    """Sum of squares of each leaf, by leaf path."""
    return dict(zip(leaf_paths(tree),
                    (jnp.sum(a * a) for a in jax.tree_util.tree_leaves(tree))))


def _at(tree, keys):
    for k in keys:
        tree = tree[k]
    return tree


class Reference:
    """Trains a float32 copy of the weights drawn from ``seed`` (shapes
    from ``shapes``, the program's params tree) step by step.

    On several devices every weight, moment and gradient is split along
    its first axis over them, and each block's activations along the
    dimension its attention does not run over (T in spatial blocks, S in
    temporal ones): the devices share each block's work instead of taking
    turns."""

    TOP = ("embed", "final_norm", "head", "t_proj")

    def __init__(self, m: Model, opt: Optimizer, seed: int, shapes, *,
                 precision: str = "f32",
                 devices: Optional[Sequence] = None):
        self.m, self.opt, self.seed, self.shapes = m, opt, seed, shapes
        self.precision = precision
        devices = list(devices or jax.devices())
        self.n_dev = len(devices)
        self.mesh = Mesh(np.array(devices), ("r",))
        self.n_blocks = m.n_layers
        drawn = init_params(seed, shapes)
        self.top = self._put(_f32({k: drawn[k] for k in self.TOP}))
        self.blocks = []
        for j in range(self.n_blocks):
            kind = "spatial" if j % 2 == 0 else "temporal"
            bp = jax.tree_util.tree_map(lambda a: a[j // 2],
                                        drawn["layers"][kind])
            self.blocks.append(self._put(_f32(bp)))
        del drawn
        names = jax.tree_util.tree_map(lambda a: jnp.dtype(a.dtype).name,
                                       shapes)
        self._dtypes = {"top": {k: names[k] for k in self.TOP},
                        1: names["layers"]["temporal"],
                        2: names["layers"]["spatial"]}
        self.step_no = 0
        self.moments = None
        self._fns = self._build()

    def _split(self, a, dim):
        spec = [None] * a.ndim
        if a.ndim > dim and a.shape[dim] % self.n_dev == 0:
            spec[dim] = "r"
        return NamedSharding(self.mesh, P(*spec))

    def _put(self, tree, dim=0):
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._split(a, dim)), tree)

    def _rep(self, tree):
        rep = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, rep), tree)

    def _build(self):
        m, pr, dt, n = self.m, self.precision, self._dtypes, self.n_dev
        fns = {}
        for axis in (1, 2):
            def f(p, x, te, axis=axis):
                return block(_stored(p, dt[axis]), x, te, axis=axis, m=m,
                             precision=pr, shards=n)
            fns[("fwd", axis)] = jax.jit(f)

            def vjp(p, x, te, g, f=f):
                _, back = jax.vjp(f, p, x, te)
                return back(g)
            fns[("bwd", axis)] = jax.jit(vjp)

        def start(top, x, t):
            top = _stored(top, dt["top"])
            return (embed(top["embed"], x, pr),
                    timestep(top["t_proj"], t, m.d_model, pr))

        def finish(top, x, target):
            return jax.value_and_grad(
                lambda tp, xx: head_loss(_stored(tp, dt["top"]), xx, target,
                                         pr),
                argnums=(0, 1))(top, x)

        def start_bwd(top, x, t, gx, gte):
            _, back = jax.vjp(lambda tp: start(tp, x, t), top)
            return back((gx, gte))[0]

        fns["start"] = jax.jit(start)
        fns["finish"] = jax.jit(finish)
        fns["start_bwd"] = jax.jit(start_bwd)
        fns["sq"] = jax.jit(_sq)
        fns["adam"] = jax.jit(self._adam, donate_argnums=(0, 2, 3))
        return fns

    def _act(self, x, j):
        """Block ``j``'s input, split along the dimension its attention
        does not run over."""
        return jax.device_put(x, self._split(x, 1 if j % 2 == 0 else 2))

    def loss_and_grads(self, batch):
        f = self._fns
        batch = self._rep(batch)
        x, te = f["start"](self.top, batch["x"], batch["t"])
        te = self._rep(te)
        inputs = []
        for j, bp in enumerate(self.blocks):
            x = self._act(x, j)
            inputs.append(x)
            x = f[("fwd", 2 if j % 2 == 0 else 1)](bp, x, te)
        loss, (g_top, gx) = f["finish"](self.top, x, batch["target"])
        g_te = jnp.zeros_like(te)
        g_blocks: List[Dict] = [None] * self.n_blocks
        for j in reversed(range(self.n_blocks)):
            gp, gx, gt = f[("bwd", 2 if j % 2 == 0 else 1)](
                self.blocks[j], inputs[j], te, self._act(gx, j))
            inputs[j] = None
            g_blocks[j] = self._put(gp)
            g_te = g_te + gt
        gs = f["start_bwd"](self.top, batch["x"], batch["t"], gx, g_te)
        for k in ("embed", "t_proj"):
            g_top[k] = gs[k]
        return float(loss), self._put(g_top), g_blocks

    def _adam(self, p, g, mom, vel, lr, b1c, b2c, clip):
        o = self.opt

        def upd(p, g, mo, ve):
            g = g * clip
            mo = o.b1 * mo + (1 - o.b1) * g
            ve = o.b2 * ve + (1 - o.b2) * g * g
            p = p - lr * ((mo / b1c) / (jnp.sqrt(ve / b2c) + o.eps)
                          + o.weight_decay * p)
            return p, mo, ve
        out = jax.tree_util.tree_map(upd, p, g, mom, vel)
        pick = lambda i: jax.tree_util.tree_map(
            lambda _, t: t[i], p, out)
        return pick(0), pick(1), pick(2)

    def leaf_sq(self, top, blocks) -> Dict[str, float]:
        """Sums of squares in the program's leaf paths: a stacked leaf
        ``layers/<kind>/...`` sums over its blocks."""
        out: Dict[str, float] = {}
        for k, v in self._fns["sq"]({k: top[k] for k in self.TOP}).items():
            out[k] = float(v)
        for j, bp in enumerate(blocks):
            kind = "spatial" if j % 2 == 0 else "temporal"
            for k, v in self._fns["sq"](bp).items():
                key = f"layers/{kind}/{k}"
                out[key] = out.get(key, 0.0) + float(v)
        return out

    def train_step(self, batch):
        """One AdamW step.  Returns the loss, the squared norms of the
        clipped gradient by leaf, and the gradient before clipping as
        (top, blocks)."""
        o = self.opt
        loss, g_top, g_blocks = self.loss_and_grads(batch)
        gsq = self.leaf_sq(g_top, g_blocks)
        clip = min(1.0, o.grad_clip / (math.sqrt(sum(gsq.values())) + 1e-9))
        self.step_no += 1
        t = self.step_no
        scal = (jnp.float32(o.lr(t)), jnp.float32(1 - o.b1 ** t),
                jnp.float32(1 - o.b2 ** t), jnp.float32(clip))
        if self.moments is None:
            zeros = lambda tree: self._put(
                jax.tree_util.tree_map(jnp.zeros_like, tree))
            self.moments = ([(zeros(self.top), zeros(self.top))]
                            + [(zeros(b), zeros(b)) for b in self.blocks])
        groups = [self.top] + self.blocks
        grads = [g_top] + g_blocks
        for i, (p, g) in enumerate(zip(groups, grads)):
            mo, ve = self.moments[i]
            p, mo, ve = self._fns["adam"](p, g, mo, ve, *scal)
            self.moments[i] = (mo, ve)
            if i == 0:
                self.top = p
            else:
                self.blocks[i - 1] = p
        return (loss, {k: v * clip * clip for k, v in gsq.items()},
                (g_top, g_blocks))

    def pieces(self, top, blocks):
        """Each program leaf as the reference holds it: (leaf index, path,
        index into the program's stack of blocks or None, array), for a
        tree shaped as the weights, given as (top, blocks)."""
        for i, path in enumerate(leaf_paths(self.shapes)):
            keys = path.split("/")
            if keys[0] != "layers":
                yield i, path, None, _at(top, keys)
                continue
            kind = keys[1] == "temporal"
            for pair in range(self.n_blocks // 2):
                yield i, path, pair, _at(blocks[2 * pair + kind], keys[2:])

    def delta_pieces(self):
        """The weights' change since they were drawn, as ``pieces``."""
        first = None
        for i, path, pair, now in self.pieces(self.top, self.blocks):
            if pair in (None, 0):
                first = init_leaf(self.seed, self.shapes, i).astype(
                    jnp.float32)
            was = first if pair is None else first[pair]
            yield i, path, pair, now - jax.device_put(was, now.sharding)

    def free(self):
        for tree in ([self.top] + self.blocks
                     + [a for pair in (self.moments or []) for a in pair]):
            for a in jax.tree_util.tree_leaves(tree):
                a.delete()
