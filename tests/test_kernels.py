"""Per-kernel correctness: shape/dtype sweeps against the pure-jnp oracles
(interpret mode executes the Pallas kernel body on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ops import flash_attention, flash_tiles, ssd_scan

KEY = jax.random.PRNGKey(0)


def rand(shape, i, dtype=jnp.float32):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape, dtype)


ATTN_CASES = [
    # B, Hq, Hkv, Sq, Skv, D, causal, window, softcap
    (2, 4, 4, 128, 128, 64, False, None, None),
    (1, 8, 2, 256, 256, 32, True, None, None),       # GQA causal
    (1, 4, 1, 100, 100, 64, True, 37, None),         # MQA + window + ragged
    (1, 2, 2, 64, 192, 64, False, None, 30.0),       # softcap, cross lengths
    (2, 6, 3, 80, 80, 16, True, None, None),         # non-128 dims
    (1, 2, 2, 1, 300, 64, True, None, None),         # decode-like Sq=1
    (1, 4, 4, 128, 128, 128, True, 64, 50.0),        # everything on
    (32, 16, 16, 16, 16, 72, False, None, None),     # DiT temporal, T=16
    (1, 4, 2, 200, 600, 32, True, 256, None),        # many KV tiles, padded
    (2, 2, 2, 256, 256, 72, False, None, None),      # DiT spatial, S=256
    (1, 2, 2, 1024, 1024, 64, False, None, None),    # 3B spatial, S=1024
]


def _runs_kernel(fn, *args):
    """Whether the traced op holds the Pallas kernel (a ``pallas_call``)."""
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    b, hq, hkv, sq, skv, d, causal, window, softcap = case
    q = rand((b, hq, sq, d), 1, dtype)
    k = rand((b, hkv, skv, d), 2, dtype)
    v = rand((b, hkv, skv, d), 3, dtype)
    qoff = skv - sq if causal else 0
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, q_offset=qoff)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap, q_offset=qoff)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("skv,kernel", [(128, False), (129, True)],
                         ids=["one_block_xla", "two_blocks_kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_attention_path_follows_the_kv_block(skv, kernel, dtype):
    """Keys that fit one KV block run XLA's fused attention; one key more
    runs the kernel.  Both match the reference with causal, window,
    softcap and GQA on."""
    b, hq, hkv, sq, d = 1, 4, 2, 64, 32
    q = rand((b, hq, sq, d), 4, dtype)
    k = rand((b, hkv, skv, d), 5, dtype)
    v = rand((b, hkv, skv, d), 6, dtype)
    kw = dict(causal=True, window=48, softcap=30.0, q_offset=skv - sq)

    def f(q, k, v):
        return flash_attention(q, k, v, **kw)

    assert _runs_kernel(f, q, k, v) == kernel
    want = ref.attention_ref(q, k, v, **kw)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(f(q, k, v), np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_over_padded_short_kv(dtype):
    """The kernel itself at the DiT's temporal shape, keys padded to one
    128 block and masked by ``kv_len`` (``flash_attention`` no longer sends
    it such short keys)."""
    b, h, l, d = 4, 2, 16, 72
    q = rand((b, h, l, d), 7, dtype)
    k = rand((b, h, l, d), 8, dtype)
    v = rand((b, h, l, d), 9, dtype)
    pad = ((0, 0), (0, 0), (0, 128 - l), (0, 0))
    out = flash_attention_fwd(q, jnp.pad(k, pad), jnp.pad(v, pad),
                              kv_len=l, block_q=l)
    want = ref.attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("s_len,d,causal,tiles", [
    (256, 64, True, [(64, 64), (128, 256), (256, 128),
                     flash_tiles(256, 256)]),
    (1024, 64, False, [(256, 256), (1024, 512),
                       flash_tiles(1024, 1024)]),
], ids=["S256-causal", "S1024"])
def test_flash_attention_block_shapes(s_len, d, causal, tiles):
    """Same numerics across VMEM tiling choices, the rule's among them."""
    q, k, v = (rand((1, 2, s_len, d), i) for i in range(3))
    base = flash_attention_fwd(q, k, v, causal=causal, block_q=128,
                               block_k=128)
    for bq, bk in tiles:
        out = flash_attention_fwd(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-5, rtol=1e-5)


def test_flash_tiles_follow_the_shape():
    """Tiles come from the lengths alone: the cells' spatial lengths,
    keys one past a block, decode, and never padding past a multiple of
    128."""
    assert flash_tiles(256, 256) == (256, 256)
    assert flash_tiles(1024, 1024) == (1024, 1024)
    assert flash_tiles(129, 129)[1] == 256
    assert flash_tiles(1, 300) == (8, 128)
    for sq in (129, 200, 256, 384, 640, 1000, 1024, 1536, 4096):
        for skv in (129, 300, 512, 768, 1024, 2000, 4096):
            bq, bk = flash_tiles(sq, skv)
            assert -(-sq // bq) * bq == -(-sq // 128) * 128, (sq, bq)
            assert -(-skv // bk) * bk == -(-skv // 128) * 128, (skv, bk)


def _eqns(jaxpr, name):
    """Every equation of primitive ``name`` in ``jaxpr`` and the jaxprs
    nested in it."""
    for e in jaxpr.eqns:
        if e.primitive.name == name:
            yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,s_len,d,causal,window", [
    (2, 2, 2, 256, 72, False, None), (1, 2, 2, 1024, 64, False, None),
    (1, 4, 2, 600, 32, True, 256),
], ids=["S256", "S1024", "many-kv-tiles"])
def test_kernel_dots_take_f32_at_highest(b, hq, hkv, s_len, d, causal,
                                         window, dtype):
    """Both of the kernel's dots take f32 operands at ``HIGHEST``.  At the
    default precision the TPU rounds an f32 operand (q, k, p, v) to one
    bf16; interpret mode computes in f32 either way, so the numbers cannot
    show it."""
    q = rand((b, hq, s_len, d), 50, dtype)
    k, v = (rand((b, hkv, s_len, d), 51 + i, dtype) for i in range(2))
    jaxpr = jax.make_jaxpr(functools.partial(
        flash_attention, causal=causal, window=window))(q, k, v)
    kernels = list(_eqns(jaxpr.jaxpr, "pallas_call"))
    assert len(kernels) == 1
    dots = list(_eqns(kernels[0].params["jaxpr"], "dot_general"))
    assert len(dots) == 2
    highest = jax.lax.Precision.HIGHEST
    for e in dots:
        assert [x.aval.dtype for x in e.invars] == [jnp.float32] * 2
        assert e.params["precision"] == (highest, highest)


def test_flash_attention_grads_match_ref():
    q, k, v = (rand((1, 2, 64, 32), 10 + i) for i in range(3))

    def f_kernel(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    def f_ref(q, k, v):
        return ref.attention_ref(q, k, v, causal=True).sum()

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", [
    # B, Hq, Hkv, L, D, causal, window, softcap
    (8, 16, 16, 16, 72, False, None, None),          # DiT temporal
    (2, 4, 2, 100, 32, True, 37, 30.0),              # GQA, everything on
])
def test_one_block_attention_grads_match_ref(case):
    b, hq, hkv, l, d, causal, window, softcap = case
    q = rand((b, hq, l, d), 11)
    k = rand((b, hkv, l, d), 12)
    v = rand((b, hkv, l, d), 13)
    kw = dict(causal=causal, window=window, softcap=softcap)

    def f_xla(q, k, v):
        return (flash_attention(q, k, v, **kw) * v[:, :1]).sum()

    def f_ref(q, k, v):
        return (ref.attention_ref(q, k, v, **kw) * v[:, :1]).sum()

    assert not _runs_kernel(jax.grad(f_xla, argnums=(0, 1, 2)), q, k, v)
    gx = jax.grad(f_xla, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gx, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4, rtol=1e-4)


SSD_CASES = [
    # B, L, H, P, G, S, chunk
    (2, 128, 4, 16, 2, 32, 64),
    (1, 64, 2, 32, 1, 16, 16),      # MQA-style single group
    (1, 200, 4, 16, 4, 32, 64),     # ragged L (padding path)
    (2, 96, 8, 8, 2, 64, 32),
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(case, dtype):
    b, l, h, p, g, s, chunk = case
    x = rand((b, l, h, p), 20, dtype)
    dt = jax.nn.softplus(rand((b, l, h), 21)).astype(dtype)
    a = -jnp.exp(rand((h,), 22) * 0.5)
    bm = rand((b, l, g, s), 23, dtype)
    cm = rand((b, l, g, s), 24, dtype)
    dskip = rand((h,), 25)
    y = ssd_scan(x, dt, a, bm, cm, dskip, chunk=chunk)
    want = ref.ssd_ref(x, dt, a, bm, cm, d_skip=dskip)
    scale = float(jnp.abs(want.astype(jnp.float32)).max()) + 1e-6
    err = float(jnp.abs(y.astype(jnp.float32) -
                        want.astype(jnp.float32)).max()) / scale
    assert err < (3e-2 if dtype == jnp.bfloat16 else 1e-5), err


def test_ssd_chunk_invariance():
    b, l, h, p, g, s = 1, 128, 2, 16, 1, 32
    x = rand((b, l, h, p), 30)
    dt = jax.nn.softplus(rand((b, l, h), 31))
    a = -jnp.exp(rand((h,), 32) * 0.5)
    bm, cm = rand((b, l, g, s), 33), rand((b, l, g, s), 34)
    outs = [ssd_scan(x, dt, a, bm, cm, chunk=c) for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=1e-4, rtol=1e-3)


def test_ssd_matches_decode_recurrence():
    """Chunked scan == token-by-token decode recurrence (ref oracle is the
    literal recurrence, so this pins the decode/train consistency)."""
    b, l, h, p, g, s = 1, 32, 2, 8, 1, 16
    x = rand((b, l, h, p), 40)
    dt = jax.nn.softplus(rand((b, l, h), 41))
    a = -jnp.exp(rand((h,), 42) * 0.5)
    bm, cm = rand((b, l, g, s), 43), rand((b, l, g, s), 44)
    y, final = ref.ssd_ref(x, dt, a, bm, cm, return_state=True)
    yk = ssd_scan(x, dt, a, bm, cm, chunk=16)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y), atol=1e-4,
                               rtol=1e-4)
    # splitting the sequence and carrying the state matches too
    y1, st = ref.ssd_ref(x[:, :16], dt[:, :16], a, bm[:, :16], cm[:, :16],
                         return_state=True)
    y2 = ref.ssd_ref(x[:, 16:], dt[:, 16:], a, bm[:, 16:], cm[:, 16:],
                     init_state=st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y), atol=1e-4, rtol=1e-4)
