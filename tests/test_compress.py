"""Compressor math for the pod-axis gradient collectives
(``train/compress.py``), previously untested:

* ``topk_compress`` selects *exactly* k entries per leaf — regression
  for the tie over-selection and the zero-threshold case (a mostly-zero
  leaf whose k-th largest |g| is 0 used to select the entire tensor,
  silently degrading the collective back to dense);
* the error-feedback invariant ``sent + new_err == g + old_err`` holds
  bit-for-bit, and residual accumulation telescopes over steps;
* ``mode="none"`` is a plain fp32 pmean;
* the bf16 collective reduces at bf16 width in the *lowered* HLO (the
  cast must precede the pmean; XLA:CPU float-normalization promotes the
  compiled reduce to f32, so the wire-width claim is asserted on the
  pre-optimization module through
  ``repro.analysis.contracts.assert_collective_width``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis.contracts import assert_collective_width
from repro.launch.mesh import make_mesh
from repro.train.compress import (bf16_compress, compressed_psum,
                                  init_error_state, topk_compress)


def _tree(rng):
    return {"w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(24,)), jnp.float32)}


# ---------------------------------------------------------------------------
# top-k selection size
# ---------------------------------------------------------------------------

def test_topk_never_selects_more_than_k_on_zero_threshold():
    """Mostly-zero leaf (sparse/embedding-style): the k-th largest |g| is
    0, and the old `abs >= thresh` mask selected the whole tensor."""
    g = {"emb": jnp.zeros((100,), jnp.float32).at[jnp.asarray([3, 50, 97])]
         .set(jnp.asarray([1.0, -2.0, 0.5]))}
    err = init_error_state(g)
    sent, new_err = topk_compress(g, err, k_frac=0.1)     # k = 10
    nz = int((sent["emb"] != 0).sum())
    assert nz <= 10, f"transmitted {nz} > k=10 entries"
    # the real (nonzero) entries must all be selected
    assert float(sent["emb"][3]) == 1.0
    assert float(sent["emb"][50]) == -2.0
    assert float(sent["emb"][97]) == 0.5


def test_topk_exact_k_on_ties():
    """All-equal magnitudes: a threshold mask keeps every entry; the
    index-scatter form keeps exactly k."""
    g = {"w": jnp.ones((20,), jnp.float32)}
    sent, _ = topk_compress(g, init_error_state(g), k_frac=0.25)  # k = 5
    assert int((sent["w"] != 0).sum()) == 5


def test_topk_k_floor_is_one():
    g = {"w": jnp.asarray([0.5, -3.0], jnp.float32)}
    sent, _ = topk_compress(g, init_error_state(g), k_frac=0.0)
    assert int((sent["w"] != 0).sum()) == 1
    assert float(sent["w"][1]) == -3.0


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------

def test_error_feedback_invariant_bitwise():
    """sent + new_err == g + old_err, exactly (same fp additions on both
    sides: the residual is flat - sent with sent a masked copy)."""
    rng = np.random.default_rng(0)
    g = _tree(rng)
    err = jax.tree.map(
        lambda l: jnp.asarray(rng.normal(size=l.shape) * 0.1, jnp.float32),
        g)
    sent, new_err = topk_compress(g, err, k_frac=0.2)
    for k in g:
        lhs = np.asarray(sent[k] + new_err[k])
        rhs = np.asarray(g[k] + err[k])
        assert np.array_equal(lhs, rhs), k


def test_error_feedback_residual_telescopes_over_steps():
    """Over T steps, cumulative transmitted mass equals cumulative
    gradient mass minus the final residual, exactly per step — nothing
    is ever dropped, only delayed."""
    rng = np.random.default_rng(1)
    err = {"w": jnp.zeros((32,), jnp.float32)}
    sent_sum = np.zeros((32,), np.float64)
    g_sum = np.zeros((32,), np.float64)
    for t in range(6):
        g = {"w": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
        sent, err = topk_compress(g, err, k_frac=0.1)
        sent_sum += np.asarray(sent["w"], np.float64)
        g_sum += np.asarray(g["w"], np.float64)
    assert np.allclose(sent_sum + np.asarray(err["w"], np.float64), g_sum,
                       atol=1e-5)
    # the residual is actually doing work: some mass is still pending
    assert float(np.abs(np.asarray(err["w"])).max()) > 0.0


def test_init_error_state_pod_leading_dim():
    p = {"w": jnp.zeros((4, 3)), "b": jnp.zeros((5,))}
    e = init_error_state(p, n_pods=2)
    assert e["w"].shape == (2, 4, 3) and e["b"].shape == (2, 5)
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(e))
    e1 = init_error_state(p)
    assert e1["w"].shape == (4, 3)


# ---------------------------------------------------------------------------
# compressed_psum modes (axis bound by vmap, as the engine does)
# ---------------------------------------------------------------------------

def _vmapped_psum(g_stacked, mode, err=None, k_frac=0.05):
    def per_pod(g, e):
        red, e_new = compressed_psum(g, "pod", mode, err=e, k_frac=k_frac)
        return red, e_new
    return jax.vmap(per_pod, in_axes=(0, 0), out_axes=(None, 0),
                    axis_name="pod")(g_stacked, err)


def test_mode_none_is_plain_fp32_pmean():
    rng = np.random.default_rng(2)
    g = {"w": jnp.asarray(rng.normal(size=(4, 8, 3)), jnp.bfloat16)}
    red, err = _vmapped_psum(g, "none")
    assert err is None
    assert red["w"].dtype == jnp.float32
    want = np.asarray(g["w"].astype(jnp.float32)).mean(0)
    assert np.allclose(np.asarray(red["w"]), want, atol=1e-6)


def test_mode_bf16_reduces_bf16_values():
    rng = np.random.default_rng(3)
    g = {"w": jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)}
    red, _ = _vmapped_psum(g, "bf16")
    assert red["w"].dtype == jnp.float32
    # mean of bf16-rounded values, computed at bf16 precision
    want = np.asarray(g["w"].astype(jnp.bfloat16)).mean(0)
    assert np.allclose(np.asarray(red["w"]), want, atol=0.05)


def test_mode_topk_mean_of_sent():
    g = {"w": jnp.asarray([[4.0, 0.1, 0.0, 0.2],
                           [0.3, -8.0, 0.1, 0.0]], jnp.float32)}
    err = {"w": jnp.zeros((2, 4), jnp.float32)}
    red, new_err = _vmapped_psum(g, "topk", err=err, k_frac=0.25)  # k=1
    # each pod sends only its single largest entry; the mean keeps zeros
    # elsewhere
    assert np.allclose(np.asarray(red["w"]), [2.0, -4.0, 0.0, 0.0])
    assert new_err["w"].shape == (2, 4)


def test_unknown_mode_raises():
    with pytest.raises(ValueError):
        compressed_psum({"w": jnp.zeros((2,))}, "pod", mode="int4")


# ---------------------------------------------------------------------------
# reduce dtype in the lowered HLO
# ---------------------------------------------------------------------------

def _lowered_compressed_psum(mode):
    """Lowered module of a shard_map'd compressed_psum (1-device 'pod'
    mesh: lowering — unlike compilation — still emits the collective)."""
    mesh = make_mesh((1,), ("pod",))

    def f(g):
        red, _ = compressed_psum(g, "pod", mode=mode)
        return red

    sm = jax.shard_map(f, mesh=mesh, in_specs=({"w": P("pod")},),
                       out_specs={"w": P("pod")})
    return jax.jit(sm).lower({"w": jnp.ones((8, 4), jnp.float32)})


def test_bf16_collective_reduces_at_bf16_width_in_lowered_hlo():
    assert_collective_width(_lowered_compressed_psum("bf16"), dtype="bf16")


def test_none_collective_reduces_at_f32_width_in_lowered_hlo():
    assert_collective_width(_lowered_compressed_psum("none"), dtype="f32")


def test_bf16_compress_casts_only():
    g = {"w": jnp.asarray([1.0, 2.5], jnp.float32)}
    c = bf16_compress(g)
    assert c["w"].dtype == jnp.bfloat16
