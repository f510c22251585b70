"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp
oracle, per the deliverable-c requirement.  Everything here carries the
``kernel`` marker (and none is ``slow``), so the fast tier
(``pytest -m "not slow"``) covers the whole sweep and ``-m kernel``
selects just it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import rand_cases

pytestmark = pytest.mark.kernel

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0, seed=None):
    rng = np.random.default_rng(seed) if seed is not None else RNG
    return jnp.asarray(rng.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
# grad_sketch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "N,d,V,k1,k2,tn,tv,dtype",
    [(40, 32, 300, 16, 16, 16, 128, jnp.float32),
     (256, 64, 1000, 32, 32, 128, 256, jnp.float32),
     (100, 48, 517, 8, 24, 32, 100, jnp.float32),
     (64, 32, 301, 16, 16, 32, 64, jnp.bfloat16),
     (17, 16, 64, 8, 8, 8, 32, jnp.float32)])
def test_grad_sketch_matches_oracle(N, d, V, k1, k2, tn, tv, dtype):
    from repro.kernels.grad_sketch.kernel import grad_sketch
    from repro.kernels.grad_sketch.ref import grad_sketch_ref
    h = _arr((N, d), dtype)
    w = _arr((d, V), dtype, 0.1)
    rh, rv = _arr((d, k1)), _arr((V, k2))
    t = jnp.asarray(RNG.integers(0, V, N), jnp.int32)
    s = jnp.asarray(RNG.uniform(0.5, 1.0, N), jnp.float32)
    want = grad_sketch_ref(h, w, rh, rv, t, s)
    got = grad_sketch(h, w, rh, rv, t, s, tn=tn, tv=tv, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    rel = float(jnp.abs(got - want).max() / (jnp.abs(want).max() + 1e-9))
    assert rel < tol, rel


def test_grad_sketch_op_jnp_path_matches():
    from repro.kernels.grad_sketch.ops import grad_sketch_op
    from repro.kernels.grad_sketch.ref import grad_sketch_ref
    h, w = _arr((50, 24)), _arr((24, 400), scale=0.1)
    rh, rv = _arr((24, 12)), _arr((400, 12))
    t = jnp.asarray(RNG.integers(0, 400, 50), jnp.int32)
    s = jnp.ones((50,), jnp.float32)
    want = grad_sketch_ref(h, w, rh, rv, t, s)
    got = grad_sketch_op(h, w, rh, rv, t, s, use_pallas=False, vocab_chunk=128)
    assert jnp.allclose(got, want, atol=1e-4)


# ---------------------------------------------------------------------------
# omp_gram
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,D,tn,td,dtype",
                         [(40, 100, 16, 64, jnp.float32),
                          (130, 257, 64, 64, jnp.float32),
                          (64, 128, 32, 128, jnp.bfloat16),
                          (7, 9, 8, 8, jnp.float32)])
def test_omp_gram_matches_oracle(n, D, tn, td, dtype):
    from repro.kernels.omp_gram.kernel import omp_gram
    from repro.kernels.omp_gram.ref import omp_gram_ref
    g = _arr((n, D), dtype)
    got = omp_gram(g, tn=tn, td=td, interpret=True)
    want = omp_gram_ref(g)
    tol = 1e-3 if dtype == jnp.float32 else 1e-1
    assert jnp.allclose(got, want, atol=tol), float(jnp.abs(got - want).max())


# ---------------------------------------------------------------------------
# swa_attn
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,S,hd,W,tq,dtype",
                         [(2, 3, 128, 32, 32, 16, jnp.float32),
                          (1, 2, 256, 64, 64, 32, jnp.float32),
                          (2, 2, 64, 16, 16, 16, jnp.float32),
                          (1, 2, 128, 32, 64, 32, jnp.bfloat16),
                          (1, 1, 96, 16, 32, 32, jnp.float32)])
def test_swa_attn_matches_oracle(B, H, S, hd, W, tq, dtype):
    from repro.kernels.swa_attn.kernel import swa_attn
    from repro.kernels.swa_attn.ref import swa_attn_ref
    q, k, v = (_arr((B, H, S, hd), dtype) for _ in range(3))
    got = swa_attn(q, k, v, window=W, tq=tq, interpret=True)
    want = swa_attn_ref(q, k, v, window=W)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    assert jnp.allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                        atol=tol)


# ---------------------------------------------------------------------------
# rnnt_lattice
# ---------------------------------------------------------------------------
NEG = -1e30


def _lattice_inputs(T, B, U1, seed):
    """Random lattice rows with the kernel's structural invariants:
    emit[:, :, 0] = NEG, sparse additive seeds like the alpha/beta uses."""
    rng = np.random.default_rng(seed)
    mult = jnp.asarray(rng.normal(size=(T, B, U1)), jnp.float32)
    add = jnp.where(jnp.asarray(rng.uniform(size=(T, B, U1))) < 0.3,
                    jnp.asarray(rng.normal(size=(T, B, U1)), jnp.float32),
                    NEG)
    emit = jnp.asarray(rng.normal(size=(T, B, U1)),
                       jnp.float32).at[:, :, 0].set(NEG)
    return mult, add, emit


@pytest.mark.parametrize("T,B,U1",
                         [(1, 1, 1), (5, 2, 2), (7, 3, 5), (12, 2, 8),
                          (4, 4, 17), (9, 1, 33)])
def test_rnnt_lattice_matches_oracle(T, B, U1):
    from repro.kernels.rnnt_lattice.kernel import rnnt_lattice
    from repro.kernels.rnnt_lattice.ref import rnnt_lattice_ref
    mult, add, emit = _lattice_inputs(T, B, U1, seed=T * 100 + U1)
    got = rnnt_lattice(mult, add, emit, interpret=True)
    want = rnnt_lattice_ref(mult, add, emit)
    assert got.shape == (T, B, U1)
    assert jnp.allclose(got, want, atol=1e-4), \
        float(jnp.abs(got - want).max())


def test_rnnt_lattice_op_dispatch_matches():
    from repro.kernels.rnnt_lattice.ops import rnnt_lattice_op
    from repro.kernels.rnnt_lattice.ref import rnnt_lattice_ref
    mult, add, emit = _lattice_inputs(6, 2, 4, seed=0)
    want = rnnt_lattice_ref(mult, add, emit)
    got_ref = rnnt_lattice_op(mult, add, emit, use_pallas=False)
    got_pal = rnnt_lattice_op(mult, add, emit, use_pallas=True,
                              interpret=True)
    assert jnp.allclose(got_ref, want, atol=1e-5)
    assert jnp.allclose(got_pal, want, atol=1e-4)


def test_rnnt_lattice_kernel_through_fused_loss():
    """End to end: the fused transducer loss with the interpret-mode
    Pallas lattice agrees with the dense oracle on values and head
    gradients (ragged lengths included)."""
    from repro.core.rnnt_loss import rnnt_loss_from_logits, rnnt_loss_fused
    rng = np.random.default_rng(3)
    B, T, U, J, V = 3, 6, 4, 5, 11
    ze = jnp.asarray(rng.normal(size=(B, T, J)), jnp.float32)
    zp = jnp.asarray(rng.normal(size=(B, U + 1, J)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(J, V)) * 0.5, jnp.float32)
    labels = jnp.asarray(rng.integers(1, V, (B, U)), jnp.int32)
    t_lens = jnp.asarray([6, 1, 4], jnp.int32)
    u_lens = jnp.asarray([4, 0, 2], jnp.int32)

    def dense(w):
        logits = jnp.tanh(ze[:, :, None, :] + zp[:, None, :, :]) @ w
        return rnnt_loss_from_logits(logits, labels, t_lens, u_lens)

    fused = lambda w: rnnt_loss_fused(ze, zp, w, labels, t_lens, u_lens,
                                      lattice_impl="interpret")
    assert jnp.allclose(fused(w), dense(w), atol=1e-5)
    gd = jax.grad(lambda w: dense(w).sum())(w)
    gf = jax.grad(lambda w: fused(w).sum())(w)
    rel = float(jnp.abs(gf - gd).max() / (jnp.abs(gd).max() + 1e-9))
    assert rel < 1e-4, rel


# ---------------------------------------------------------------------------
# rwkv6 chunked WKV
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,N,C",
                         [(2, 64, 2, 16, 16), (1, 128, 3, 32, 32),
                          (2, 96, 1, 8, 32), (1, 64, 2, 64, 64)])
def test_rwkv6_wkv_matches_sequential(B, S, H, N, C):
    from repro.kernels.rwkv6_scan.kernel import rwkv6_wkv
    from repro.kernels.rwkv6_scan.ref import rwkv6_wkv_ref
    r, k, v = (_arr((B, S, H, N)) for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.4, 0.99, (B, S, H, N)), jnp.float32)
    u = _arr((H, N), scale=0.1)
    y_got, s_got = rwkv6_wkv(r, k, v, w, u, chunk=C, interpret=True)
    y_want, s_want = rwkv6_wkv_ref(r, k, v, w, u)
    assert jnp.allclose(y_got, y_want, atol=1e-3)
    assert jnp.allclose(s_got, s_want, atol=1e-3)


def test_rwkv6_extreme_decays_stable():
    """Near-zero decays (log w very negative) must not overflow/NaN."""
    from repro.kernels.rwkv6_scan.kernel import rwkv6_wkv
    B, S, H, N = 1, 64, 1, 8
    r, k, v = (_arr((B, S, H, N)) for _ in range(3))
    w = jnp.full((B, S, H, N), 1e-6)
    y, s = rwkv6_wkv(r, k, v, w, _arr((H, N)), chunk=16, interpret=True)
    assert jnp.isfinite(y).all() and jnp.isfinite(s).all()
