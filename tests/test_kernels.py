"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode on CPU), per the kernels/<name>/{kernel,ops,ref} contract."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attn import decode_attn, decode_attn_ref
from repro.kernels.decode_attn.ops import decode_attention as decode_attn_op
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rmsnorm.rmsnorm import rmsnorm
from repro.kernels.wagg import (auto_block_n, wagg, wagg_fused,
                                wagg_fused_ref, wagg_ref)


# -- wagg -------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("p,n,bn", [(2, 64, 64), (8, 1000, 256),
                                    (16, 4096, 512), (32, 333, 128)])
def test_wagg_sweep(p, n, bn, dtype):
    key = jax.random.key(p * n)
    x = jax.random.normal(key, (p, n), dtype)
    theta = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (p,)))
    for beta in (0.0, 0.5, 1.0):
        out = wagg(x, theta, beta, block_n=bn)
        ref = wagg_ref(x, theta, beta)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)


@settings(max_examples=25, deadline=None)
@given(p=st.integers(2, 16), n=st.integers(1, 300),
       beta=st.floats(0.0, 1.0), seed=st.integers(0, 99))
def test_hyp_wagg_arbitrary_shapes(p, n, beta, seed):
    key = jax.random.key(seed)
    x = jax.random.normal(key, (p, n), jnp.float32)
    theta = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (p,)))
    out = wagg(x, theta, beta, block_n=128)
    np.testing.assert_allclose(out, wagg_ref(x, theta, beta),
                               rtol=1e-4, atol=1e-5)


# -- wagg v2: fused dequant + mask + Eq. 10 -----------------------------------------

def _fused_case(seed=0, p=6, n=1000):
    """p=6 (not a power of two) and n=1000 with block_n=256 (padded tail)."""
    key = jax.random.key(seed)
    x = jax.random.normal(key, (p, n), jnp.float32)
    theta = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1),
                                             (p,)))
    return x, theta


@pytest.mark.parametrize("codec_name", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("masked", [False, True])
def test_wagg_fused_codec_parity(codec_name, masked):
    """The fused kernel consuming quantized payload tiles stays within the
    codec's documented error bound of the f32 reference — the same contract
    the composition grid holds the composed backend to, here at the kernel
    level, with a padded tail and p not a power of two."""
    from repro.core.codecs import get_codec
    x, theta = _fused_case()
    p = x.shape[0]
    codec = get_codec(codec_name)
    payload, aux = codec.encode(x)
    theta_eff = theta if aux is None else theta * jnp.float32(aux)
    active = None
    beta_eff = 0.9
    if masked:
        active = jnp.asarray(np.arange(p) % 3 != 1, jnp.float32)
        beta_eff = 1.0                  # late-join rows adopt m wholesale
    out = wagg_fused(x, theta_eff, 0.9, payload=payload, active=active,
                     block_n=256)
    ref = wagg_fused_ref(x, theta, 0.9)   # f32, no payload
    if masked:
        ref = jnp.where(active[:, None] != 0, ref,
                        jnp.einsum("p,pn->n", theta, x)[None, :])
    tol = float(codec.error_bound(x, theta, beta_eff))
    err = float(jnp.abs(out - ref).max())
    assert err <= tol, (codec_name, masked, err, tol)


def test_wagg_fused_matches_its_reference():
    """wagg_fused == wagg_fused_ref exactly (same payload, same mask), on
    the padded-tail fixture."""
    from repro.core.codecs import get_codec
    x, theta = _fused_case(1)
    payload, aux = get_codec("int8").encode(x)
    theta_eff = theta * jnp.float32(aux)
    active = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0, 1.0])
    out = wagg_fused(x, theta_eff, 0.7, payload=payload, active=active,
                     block_n=256)
    ref = wagg_fused_ref(x, theta_eff, 0.7, payload=payload, active=active)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_wagg_fused_beta_endpoints():
    """beta=0 is the identity on active rows; beta=1 makes every row the
    aggregate m (masked or not — late-join and FMA coincide)."""
    x, theta = _fused_case(2, p=5, n=333)
    out0 = wagg_fused(x, theta, 0.0, block_n=128)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(x))
    out1 = wagg_fused(x, theta, 1.0, block_n=128)
    m = np.einsum("p,pn->n", np.asarray(theta), np.asarray(x))
    for i in range(x.shape[0]):
        np.testing.assert_allclose(np.asarray(out1)[i], m, rtol=1e-5,
                                   atol=1e-6)


def test_wagg_interpret_default_tracks_backend():
    """Regression: ``interpret`` was hardcoded True, so the compiled kernel
    never ran even on a real TPU. The default must be None (resolved from
    jax.default_backend() at call time)."""
    import inspect
    assert inspect.signature(wagg).parameters["interpret"].default is None
    assert inspect.signature(wagg_fused).parameters["interpret"].default \
        is None


def test_auto_block_n_budget_guard():
    """The VMEM guard: small p keeps the requested block; a wide worker axis
    auto-shrinks block_n until the tile set fits the budget, never below
    the 128 floor."""
    assert auto_block_n(8, 8192, 8) == 8192
    bn = auto_block_n(4096, 8192, 8)
    assert bn < 8192 and bn >= 128
    assert bn * 4096 * 8 <= 8 * 1024 * 1024 or bn == 128
    assert auto_block_n(1 << 20, 8192, 8) == 128    # floor, never 0


def test_wagg_fused_shrink_path_correct():
    """p=300 at block_n=4096 overflows the 8 MiB budget (300*4096*8 ≈ 9.8
    MiB) — the guard shrinks the block and the result must still match."""
    key = jax.random.key(5)
    p, n = 300, 4096
    x = jax.random.normal(key, (p, n), jnp.float32)
    theta = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1),
                                             (p,)))
    assert auto_block_n(p, 4096, 8) < 4096
    out = wagg(x, theta, 0.9, block_n=4096)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(wagg_ref(x, theta, 0.9)),
                               rtol=1e-4, atol=1e-5)


# -- decode_attn ---------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,kv,g,hd,S,bs", [
    (1, 1, 4, 64, 128, 64),
    (2, 2, 4, 32, 300, 128),
    (2, 8, 1, 128, 256, 256),   # MHA-style
    (1, 1, 8, 256, 700, 512),   # gemma-style kv=1
])
def test_decode_attn_sweep(b, kv, g, hd, S, bs, dtype):
    key = jax.random.key(b + S)
    q = jax.random.normal(key, (b, kv, g, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, S, kv, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, S, kv, hd), dtype)
    for cl in (1, S // 2, S):
        out = decode_attn(q, k, v, jnp.int32(cl), block_s=bs)
        ref = decode_attn_ref(q, k, v, jnp.int32(cl))
        tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)


def test_decode_attn_window_sweep():
    b, kv, g, hd, S = 1, 2, 2, 32, 200
    key = jax.random.key(7)
    q = jax.random.normal(key, (b, kv, g, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, S, kv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, S, kv, hd))
    for cl, win in [(10, 4), (150, 64), (200, 128), (200, 1)]:
        out = decode_attn(q, k, v, jnp.int32(cl), window=win, block_s=64)
        ref = decode_attn_ref(q, k, v, jnp.int32(cl), window=win)
        np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_decode_attn_model_layout_wrapper():
    b, h, kv, hd, S = 2, 8, 2, 32, 96
    key = jax.random.key(9)
    q = jax.random.normal(key, (b, 1, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, S, kv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, S, kv, hd))
    out = decode_attn_op(q, k, v, jnp.int32(50))
    from repro.models.attention import decode_attention as model_ref
    ref = model_ref(q, k, v, jnp.int32(50))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# -- paged decode_attn ---------------------------------------------------------------

def _paged_setup(b, kv, g, hd, bs, nblk, seed):
    """Random pools + a shuffled block table (+ trash row at the end)."""
    from numpy.random import default_rng
    rng = default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, kv, g, hd)), jnp.float32)
    n_pool = b * nblk + 1
    kp = jnp.asarray(rng.normal(size=(n_pool, bs, kv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pool, bs, kv, hd)), jnp.float32)
    tab = jnp.asarray(rng.permutation(b * nblk).reshape(b, nblk), jnp.int32)
    return q, kp, vp, tab


# Layouts a case is checked under: linear, a ring of the table's capacity,
# and a sliding window of a third of it.
LIN_RING = ((False, None), (True, None), (True, 3))
RING = ((True, None), (True, 3))


@pytest.mark.parametrize("b,kv,g,hd,bs,nblk,index,active,layouts", [
    pytest.param(2, 1, 4, 32, 16, 4, None, None, LIN_RING,
                 id="2-1-4-32-16-4"),
    pytest.param(3, 2, 2, 16, 8, 6, None, None, LIN_RING,
                 id="3-2-2-16-8-6"),
    # Yi's grouping and head width (the DMA form), 40 pages of 16 walked 16
    # at a time: one token; a row ending mid-way through its second compute
    # block; the table's full width, whose last block is half used; an
    # inactive row
    pytest.param(4, 4, 8, 128, 16, 40, (0, 16 * 24 + 5, 639, 200),
                 (True, True, True, False), LIN_RING, id="grouped-mixed"),
    # the same walks with 32-lane heads (the BlockSpec form): 10 pages of 64
    # tokens, 4 a step, so the width is no multiple of the step
    pytest.param(4, 2, 2, 32, 64, 10, (0, 64 * 6 + 5, 639, 200),
                 (True, True, True, False), LIN_RING, id="narrow-mixed"),
    # rings past their wrap, every page live again; idle rows first and
    # between, which the DMA form's prefetch skips
    pytest.param(4, 4, 8, 128, 16, 40, (640 + 17, 2 * 640 + 400, 641, 700),
                 (False, True, False, True), RING, id="grouped-wrapped"),
    pytest.param(4, 2, 2, 32, 64, 10, (640 + 17, 2 * 640 + 400, 641, 700),
                 (False, True, False, True), RING, id="narrow-wrapped"),
])
def test_paged_decode_attn_kernel_vs_ref(b, kv, g, hd, bs, nblk, index,
                                         active, layouts):
    """Kernel against the gathered reference row for row; an inactive row
    reads nothing and gives zeros in both."""
    from repro.kernels.decode_attn import (paged_decode_attn,
                                           paged_decode_attn_ref)
    q, kp, vp, tab = _paged_setup(b, kv, g, hd, bs, nblk, seed=b)
    S = bs * nblk
    idx = jnp.asarray([(7 * i + 3) % S for i in range(b)] if index is None
                      else index, jnp.int32)
    act = None if active is None else jnp.asarray(active)
    for is_ring, window_div in layouts:
        ring = S if is_ring else None
        window = None if window_div is None else S // window_div
        out = paged_decode_attn(q, kp, vp, tab, idx, act, ring=ring,
                                window=window, interpret=True)
        ref = paged_decode_attn_ref(q, kp, vp, tab, idx, act, ring=ring,
                                    window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)
        if act is not None:
            assert not np.asarray(out)[~np.asarray(act)].any()
            assert np.asarray(out)[np.asarray(act)].any()


def test_paged_linear_matches_dense_oracle():
    """Linear layout: gathering the table must reproduce dense attention
    over the first cache_len positions."""
    from repro.kernels.decode_attn import paged_decode_attn_ref
    b, kv, g, hd, bs, nblk = 2, 2, 2, 16, 8, 4
    q, kp, vp, tab = _paged_setup(b, kv, g, hd, bs, nblk, seed=3)
    S = bs * nblk
    idx = jnp.asarray([5, 25], jnp.int32)
    out = paged_decode_attn_ref(q, kp, vp, tab, idx)
    k_lin = kp[tab].reshape(b, S, kv, hd)
    v_lin = vp[tab].reshape(b, S, kv, hd)
    for i in range(b):
        ref = decode_attn_ref(q[i:i + 1], k_lin[i:i + 1], v_lin[i:i + 1],
                              jnp.int32(int(idx[i]) + 1))
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]),
                                   rtol=3e-5, atol=3e-5)


def test_paged_ring_wraparound_matches_dense_oracle():
    """Ring layout past the wrap point: a logically-linear K/V stream laid
    onto the ring (slot = p % R) must attend over exactly the last
    ``window`` positions, matching dense attention on the compacted tail."""
    from repro.kernels.decode_attn import (paged_decode_attn,
                                           paged_decode_attn_ref)
    b, kv, g, hd, bs, nblk, window = 1, 1, 2, 16, 8, 3, 20
    R = bs * nblk                                          # 24 >= window
    key = jax.random.key(12)
    L = 61                                                 # wraps twice
    q = jax.random.normal(key, (b, kv, g, hd))
    k_seq = jax.random.normal(jax.random.fold_in(key, 1), (L, kv, hd))
    v_seq = jax.random.normal(jax.random.fold_in(key, 2), (L, kv, hd))

    kp = jnp.zeros((nblk + 1, bs, kv, hd))
    vp = jnp.zeros((nblk + 1, bs, kv, hd))
    tab = jnp.arange(nblk, dtype=jnp.int32)[None]
    for p in range(L):                    # stream tokens through the ring
        slot = p % R
        kp = kp.at[slot // bs, slot % bs].set(k_seq[p])
        vp = vp.at[slot // bs, slot % bs].set(v_seq[p])
    idx = jnp.asarray([L - 1], jnp.int32)

    # dense oracle over the last `window` tokens, compacted
    tail_k = k_seq[None, L - window:]
    tail_v = v_seq[None, L - window:]
    ref = decode_attn_ref(q, tail_k, tail_v, jnp.int32(window))

    for impl in (paged_decode_attn_ref,
                 lambda *a, **kw: paged_decode_attn(*a, interpret=True,
                                                    **kw)):
        out = impl(q, kp, vp, tab, idx, ring=R, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


# -- rmsnorm -------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,br", [((8, 64), 4), ((3, 5, 128), 8),
                                      ((1000, 96), 256)])
def test_rmsnorm_sweep(shape, br, dtype):
    key = jax.random.key(shape[-1])
    x = jax.random.normal(key, shape, dtype)
    s = jax.random.normal(jax.random.fold_in(key, 1), (shape[-1],), jnp.float32)
    out = rmsnorm(x, s, block_rows=br)
    ref = rmsnorm_ref(x, s)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_wagg_leaf_tree_integration():
    """The kernel-backed aggregate equals the einsum aggregate on a tree."""
    from repro.core import weighted_aggregate, equal_weights
    from repro.kernels.wagg.ops import wagg_leaf
    params = {"a": jax.random.normal(jax.random.key(0), (4, 3, 5)),
              "b": jax.random.normal(jax.random.key(1), (4, 7))}
    axes = {"a": ("worker", None, None), "b": ("worker", None)}
    th = equal_weights(4)
    ref = weighted_aggregate(params, axes, th, 0.8)
    out = weighted_aggregate(params, axes, th, 0.8, leaf_fn=wagg_leaf)
    for k in params:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-5, atol=1e-6)


# -- fused_ce -------------------------------------------------------------------------

from repro.kernels.fused_ce import fused_ce, fused_ce_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,v,br,bv", [(16, 64, 8, 32), (100, 500, 32, 128),
                                       (256, 1000, 64, 256)])
def test_fused_ce_sweep(t, v, br, bv, dtype):
    key = jax.random.key(t + v)
    logits = jax.random.normal(key, (t, v), dtype) * 4
    labels = jax.random.randint(jax.random.fold_in(key, 1), (t,), 0, v)
    out = fused_ce(logits, labels, block_rows=br, block_v=bv)
    ref = fused_ce_ref(logits, labels)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


@settings(max_examples=20, deadline=None)
@given(t=st.integers(1, 60), v=st.integers(2, 300), seed=st.integers(0, 50))
def test_hyp_fused_ce_arbitrary(t, v, seed):
    key = jax.random.key(seed)
    logits = jax.random.normal(key, (t, v), jnp.float32) * 3
    labels = jax.random.randint(jax.random.fold_in(key, 1), (t,), 0, v)
    out = fused_ce(logits, labels, block_rows=16, block_v=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(fused_ce_ref(logits, labels)),
                               rtol=1e-4, atol=1e-4)


# -- ssd_chunk ------------------------------------------------------------------------

from repro.kernels.ssd_chunk import ssd_chunk, ssd_chunk_ref, ssd_chunked_kernel
from repro.models.ssm import ssd_reference


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,nc,L,nh,hd,ds", [
    (1, 2, 8, 2, 4, 3),
    (2, 3, 16, 4, 8, 5),
    (1, 4, 64, 2, 64, 128),   # mamba2-370m-shaped chunk
])
def test_ssd_chunk_sweep(b, nc, L, nh, hd, ds, dtype):
    key = jax.random.key(b * L + ds)
    xs = jax.random.normal(key, (b, nc, L, nh, hd), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, nc, L, nh))).astype(dtype)
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (nh,)))
    B = jax.random.normal(jax.random.fold_in(key, 3), (b, nc, L, ds), dtype)
    C = jax.random.normal(jax.random.fold_in(key, 4), (b, nc, L, ds), dtype)
    y, st, tot = ssd_chunk(xs, dt, a, B, C)
    yr, sr, tr = ssd_chunk_ref(xs, dt, a, B, C)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(tot), np.asarray(tr), rtol=1e-4,
                               atol=1e-4)


def test_ssd_chunked_kernel_full_pipeline():
    """Kernel-backed chunked SSD == naive per-step recurrence end to end."""
    key = jax.random.key(11)
    b, s, nh, hd, ds, chunk = 2, 48, 3, 8, 5, 16
    xs = jax.random.normal(key, (b, s, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, s, nh)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (nh,)))
    B = jax.random.normal(jax.random.fold_in(key, 3), (b, s, ds))
    C = jax.random.normal(jax.random.fold_in(key, 4), (b, s, ds))
    yk, stk = ssd_chunked_kernel(xs, dt, a, B, C, chunk=chunk)
    yn, stn = ssd_reference(xs, dt, a, B, C)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yn), rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(stk), np.asarray(stn), rtol=1e-3,
                               atol=1e-3)
