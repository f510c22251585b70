"""RNN-Transducer loss (Graves 2012) in pure JAX — dense and fused paths.

Forward algorithm over the (T, U+1) lattice in log space.  The row
recursion  alpha[t,u] = logaddexp(alpha[t-1,u] + blank[t-1,u],
                                  alpha[t,u-1] + emit[t,u-1])
is evaluated with an outer ``lax.scan`` over T rows; the within-row
dependency is a first-order linear recurrence in the log semiring and is
computed with ``lax.associative_scan``:
  elements (c, b) combine as (c1+c2, logaddexp(b1+c2, b2)).
Complexity O(T*U), compile size O(1) in T and U.

Two implementations share that lattice (DESIGN.md §2):

* ``rnnt_loss`` / ``rnnt_loss_from_logits`` — the **dense oracle**: takes
  the fully materialized ``(B, T, U+1, V)`` log-softmaxed joint and
  differentiates the scan with plain autodiff.  Simple, but the joint
  tensor (and its autodiff residuals) dominate training memory — the
  exact footprint problem the source paper attributes to RNN-T
  gradients.
* ``rnnt_loss_fused`` — the production path: a ``jax.custom_vjp`` over
  the joint *factors* ``(ze, zp, w_out)``.  The forward streams the
  joint row-by-row over T (and over vocab chunks), fusing
  ``tanh(ze+zp) @ w_out``, the logsumexp denominator and the blank/label
  gathers inside the row scan, so live memory is ``O(B·U·V_chunk)`` per
  step and only ``O(B·T·U)`` lattice scalars persist.  The backward runs
  the beta lattice and emits ``d loss/d logits`` in closed form —
  occupancy ``exp(alpha + beta - log p)`` decomposed into blank/emit arc
  posteriors, minus the softmax correction — contracted on the fly into
  ``(dze, dzp, dw_out)`` without ever materializing the joint or its
  gradient.  XLA stores no per-scan-step autodiff residuals.

The lattice row update itself is pluggable: the XLA associative-scan
path below (``lattice_scan_ref``) or the Pallas wavefront kernel in
``kernels/rnnt_lattice/`` (TPU; interpret-validated on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def _log_semiring_combine(e1, e2):
    c1, b1 = e1
    c2, b2 = e2
    return c1 + c2, jnp.logaddexp(b1 + c2, b2)


def _row_update(base, emit_prev):
    """Solve a[u] = logaddexp(base[u], a[u-1] + emit_prev[u-1]) for all u.

    base, emit_prev: (..., U1).  emit_prev[..., u] is the emission score
    consumed when moving u-1 -> u (i.e. aligned so position u uses
    emit_prev[..., u]); emit_prev[..., 0] must be NEG (no predecessor).
    """
    c = emit_prev
    b = base
    _, a = jax.lax.associative_scan(_log_semiring_combine, (c, b), axis=-1)
    return a


# ---------------------------------------------------------------------------
# Generic lattice scan (shared by the alpha forward and — on flipped
# inputs — the beta backward; the Pallas ``rnnt_lattice`` kernel computes
# the same recurrence, see kernels/rnnt_lattice/ref.py)
# ---------------------------------------------------------------------------

def lattice_scan_ref(mult, add, emit):
    """rows[t] = row_update(logaddexp(rows[t-1] + mult[t], add[t]), emit[t]).

    mult, add, emit: (T, B, U1).  ``rows[-1]`` is taken as NEG (log 0),
    so ``add[0]`` seeds the first row.  ``emit[t, :, 0]`` must be NEG.
    Returns the stacked rows (T, B, U1).
    """

    def step(carry, xs):
        m, a, e = xs
        row = _row_update(jnp.logaddexp(carry + m, a), e)
        return row, row

    init = jnp.full(mult.shape[1:], NEG, mult.dtype)
    _, rows = jax.lax.scan(step, init, (mult, add, emit))
    return rows


def _lattice(mult, add, emit, impl: str, part=None):
    """Backend dispatch for the lattice scan: ``ref`` (XLA associative
    scan), ``pallas``/``interpret`` (the ``kernels/rnnt_lattice`` kernel,
    compiled / interpret-mode), or ``auto`` (Pallas on TPU, ref
    elsewhere).  ``part`` is a ``(mesh, batch axes)`` pair
    (``Sharder.batch_partition``): GSPMD cannot partition a Pallas
    kernel, so on a mesh the kernel runs under ``shard_map``, one slice
    of the batch axis per device (rows are independent across B)."""
    if impl not in ("auto", "ref", "pallas", "interpret"):
        raise ValueError(f"lattice_impl must be 'auto', 'ref', 'pallas' "
                         f"or 'interpret', got {impl!r}")
    from repro.kernels.backend import on_tpu
    from repro.kernels.rnnt_lattice.ops import rnnt_lattice_op
    if impl == "ref" or (impl == "auto" and not on_tpu()):
        return lattice_scan_ref(mult, add, emit)

    def kernel(m, a, e):
        return rnnt_lattice_op(m, a, e, use_pallas=True,
                               interpret=(impl == "interpret"))

    if part is None:
        return kernel(mult, add, emit)
    from jax.sharding import PartitionSpec as P
    mesh, axes = part
    spec = P(None, axes, None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(mult, add, emit)


# ---------------------------------------------------------------------------
# Dense oracle
# ---------------------------------------------------------------------------

def rnnt_loss(
    log_probs: jax.Array,     # (B, T, U1, V) log-softmaxed joint outputs
    labels: jax.Array,        # (B, U) int32
    t_lens: jax.Array,        # (B,) frames per example
    u_lens: jax.Array,        # (B,) labels per example
    blank: int = 0,
) -> jax.Array:
    """Per-example negative log-likelihood, shape (B,)."""
    B, T, U1, V = log_probs.shape
    U = U1 - 1
    lp = log_probs.astype(jnp.float32)

    lp_blank = lp[..., blank]                                   # (B,T,U1)
    lab = jnp.pad(labels, ((0, 0), (0, 1)))                     # (B,U1)
    lp_emit = jnp.take_along_axis(
        lp, lab[:, None, :, None].astype(jnp.int32), axis=-1)[..., 0]
    # invalidate emissions at/after u_lens (cannot emit past the last label)
    u_ids = jnp.arange(U1)
    emit_valid = u_ids[None, :] < u_lens[:, None]               # (B,U1)
    lp_emit = jnp.where(emit_valid[:, None, :], lp_emit, NEG)

    # alpha[0] row: alpha[0,0]=0; alpha[0,u] = sum_{j<u} emit[0,j]
    init_base = jnp.full((B, U1), NEG).at[:, 0].set(0.0)
    emit_shift0 = jnp.pad(lp_emit[:, 0, :-1], ((0, 0), (1, 0)),
                          constant_values=NEG)
    alpha0 = _row_update(init_base, emit_shift0)

    def row_step(alpha_prev, inputs):
        lpb_prev, lpe_t = inputs                                # (B,U1) each
        base = alpha_prev + lpb_prev                            # blank move
        emit_shift = jnp.pad(lpe_t[:, :-1], ((0, 0), (1, 0)),
                             constant_values=NEG)
        alpha_t = _row_update(base, emit_shift)
        return alpha_t, alpha_t

    xs = (jnp.moveaxis(lp_blank, 1, 0)[:-1],                    # rows 0..T-2
          jnp.moveaxis(lp_emit, 1, 0)[1:])                      # rows 1..T-1
    _, alphas_rest = jax.lax.scan(row_step, alpha0, xs)
    alphas = jnp.concatenate([alpha0[None], alphas_rest], axis=0)  # (T,B,U1)

    # NLL = -(alpha[T-1, U] + blank[T-1, U]) gathered at true lengths
    t_idx = jnp.clip(t_lens - 1, 0, T - 1)
    a_final = alphas[t_idx, jnp.arange(B)]                      # (B,U1)
    a_at_u = jnp.take_along_axis(a_final, u_lens[:, None], axis=1)[:, 0]
    b_final = jnp.take_along_axis(
        lp_blank[jnp.arange(B), t_idx], u_lens[:, None], axis=1)[:, 0]
    return -(a_at_u + b_final)


def rnnt_loss_from_logits(logits, labels, t_lens, u_lens, blank: int = 0):
    return rnnt_loss(jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
                     labels, t_lens, u_lens, blank)


# ---------------------------------------------------------------------------
# Fused loss: custom_vjp over the joint factors, vocab-streamed
# ---------------------------------------------------------------------------

def _vocab_chunks(w_out, vocab_chunk: int):
    """Pad/reshape the head to (n_chunks, J, C) plus a column-validity
    mask (n_chunks, C) — the streaming layout of the row scans, shared
    with ``core/lastlayer.py:streamed_er2`` via ``core/chunking.py`` so
    the padding/mask convention cannot drift."""
    from repro.core.chunking import resolve_vocab_chunk, vocab_chunks
    V = w_out.shape[1]
    return vocab_chunks(w_out, resolve_vocab_chunk(V, vocab_chunk), axis=1)


def _row_scores(z, wp, valid, w_blank, w_lab, emit_valid, logz_only=False):
    """One joint row: z (B,U1,J) -> (lpb, lpe, logz), each (B,U1).

    The logsumexp denominator streams over vocab chunks with an online
    (flash-style) max/sum; the blank/label scores are direct gathered
    contractions against single head columns, so the full (B,U1,V)
    logits row only ever exists one V_chunk at a time.
    """
    B, U1, _ = z.shape

    def chunk_step(carry, xs):
        m, s = carry
        wc, vc = xs
        lg = jnp.where(vc[None, None, :], jnp.einsum("buj,jc->buc", z, wc),
                       NEG)
        m_new = jnp.maximum(m, lg.max(axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(lg - m_new[..., None]).sum(-1)
        return (m_new, s), None

    m0 = jnp.full((B, U1), NEG, jnp.float32)
    s0 = jnp.zeros((B, U1), jnp.float32)
    (m, s), _ = jax.lax.scan(chunk_step, (m0, s0), (wp, valid))
    logz = m + jnp.log(jnp.maximum(s, 1e-37))
    lpb = jnp.einsum("buj,j->bu", z, w_blank) - logz
    lpe = jnp.where(emit_valid,
                    jnp.einsum("buj,buj->bu", z, w_lab) - logz, NEG)
    return lpb, lpe, logz


def _alpha_inputs(lpb, lpe):
    """Assemble (mult, add, emit) rows for the alpha lattice scan."""
    T, B, U1 = lpb.shape
    neg_row = jnp.full((1, B, U1), NEG, lpb.dtype)
    mult = jnp.concatenate([neg_row, lpb[:-1]], axis=0)
    init_base = jnp.full((B, U1), NEG).at[:, 0].set(0.0)
    add = jnp.concatenate(
        [init_base[None], jnp.full((T - 1, B, U1), NEG)], axis=0)
    emit = jnp.pad(lpe[:, :, :-1], ((0, 0), (0, 0), (1, 0)),
                   constant_values=NEG)
    return mult, add, emit


def _fused_forward(blank, vocab_chunk, impl, part, ze, zp, w_out,
                   labels, t_lens, u_lens):
    """Stream the joint over T rows -> (nll, lpb, lpe, logz, alphas)."""
    B, T, J = ze.shape
    U1 = zp.shape[1]
    wp, valid = _vocab_chunks(w_out, vocab_chunk)
    w_blank = w_out[:, blank]
    lab = jnp.pad(labels, ((0, 0), (0, 1))).astype(jnp.int32)   # (B,U1)
    w_lab = w_out.T[lab]                                        # (B,U1,J)
    emit_valid = jnp.arange(U1)[None, :] < u_lens[:, None]

    def row(_, ze_t):
        z = jnp.tanh(ze_t[:, None, :] + zp)                     # (B,U1,J)
        return None, _row_scores(z, wp, valid, w_blank, w_lab, emit_valid)

    _, (lpb, lpe, logz) = jax.lax.scan(row, None, jnp.moveaxis(ze, 1, 0))

    alphas = _lattice(*_alpha_inputs(lpb, lpe), impl, part)     # (T,B,U1)
    t_idx = jnp.clip(t_lens - 1, 0, T - 1)
    bidx = jnp.arange(B)
    a_final = alphas[t_idx, bidx]                               # (B,U1)
    a_at_u = jnp.take_along_axis(a_final, u_lens[:, None], axis=1)[:, 0]
    b_final = jnp.take_along_axis(lpb[t_idx, bidx], u_lens[:, None],
                                  axis=1)[:, 0]
    nll = -(a_at_u + b_final)
    return nll, (lpb, lpe, logz, alphas)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _rnnt_fused(blank, vocab_chunk, impl, part, ze, zp, w_out,
                labels, t_lens, u_lens):
    nll, _ = _fused_forward(blank, vocab_chunk, impl, part, ze, zp, w_out,
                            labels, t_lens, u_lens)
    return nll


def _rnnt_fused_fwd(blank, vocab_chunk, impl, part, ze, zp, w_out,
                    labels, t_lens, u_lens):
    nll, (lpb, lpe, logz, alphas) = _fused_forward(
        blank, vocab_chunk, impl, part, ze, zp, w_out, labels, t_lens,
        u_lens)
    return nll, (ze, zp, w_out, labels, t_lens, u_lens,
                 lpb, lpe, logz, alphas, nll)


def _rnnt_fused_bwd(blank, vocab_chunk, impl, part, res, g):
    """Beta lattice + closed-form occupancy gradient, streamed over T rows
    and vocab chunks into (dze, dzp, dw_out) — the (B,T,U1,V) logits
    gradient is never materialized."""
    (ze, zp, w_out, labels, t_lens, u_lens,
     lpb, lpe, logz, alphas, nll) = res
    B, T, J = ze.shape
    U1 = zp.shape[1]
    V = w_out.shape[1]

    # --- beta lattice: same recurrence on (t, u)-flipped rows, with the
    # terminal blank injected through the additive term ---------------------
    t_ids = jnp.arange(T)[:, None, None]
    u_ids = jnp.arange(U1)[None, None, :]
    terminal = ((t_ids == (t_lens - 1)[None, :, None])
                & (u_ids == u_lens[None, :, None]))             # (T,B,U1)
    term = jnp.where(terminal, lpb, NEG)
    flip = lambda x: x[::-1, :, ::-1]
    betas = flip(_lattice(flip(lpb), flip(term), flip(lpe), impl, part))

    # --- arc posteriors ----------------------------------------------------
    logp = -nll                                                 # (B,)
    neg_row = jnp.full((1, B, U1), NEG)
    beta_next_t = jnp.concatenate([betas[1:], neg_row], axis=0)
    beta_dest = jnp.logaddexp(beta_next_t, jnp.where(terminal, 0.0, NEG))
    occ_b = jnp.exp(alphas + lpb + beta_dest - logp[None, :, None])
    beta_next_u = jnp.pad(betas[:, :, 1:], ((0, 0), (0, 0), (0, 1)),
                          constant_values=NEG)
    occ_e = jnp.exp(alphas + lpe + beta_next_u - logp[None, :, None])
    gamma = occ_b + occ_e                                       # (T,B,U1)

    # --- stream d logits = p*gamma - occ_b*1_blank - occ_e*1_label into the
    # factor gradients, row by row -----------------------------------------
    wp, valid = _vocab_chunks(w_out, vocab_chunk)
    nc, _, chunk = wp.shape
    w_blank = w_out[:, blank]
    lab = jnp.pad(labels, ((0, 0), (0, 1))).astype(jnp.int32)
    w_lab = w_out.T[lab]                                        # (B,U1,J)
    gB = g.astype(jnp.float32)                                  # (B,)

    def row(carry, xs):
        dzp_acc, dwo, dwlab = carry
        ze_t, gamma_t, occb_t, occe_t, logz_t = xs
        z = jnp.tanh(ze_t[:, None, :] + zp)                     # (B,U1,J)
        coef = gamma_t * gB[:, None]                            # (B,U1)

        def chunk_step(dz, xs2):
            wc, vc = xs2
            lg = jnp.einsum("buj,jc->buc", z, wc)
            p = jnp.where(vc[None, None, :],
                          jnp.exp(lg - logz_t[..., None]), 0.0)
            pc = p * coef[..., None]                            # (B,U1,C)
            dwo_c = jnp.einsum("buj,buc->jc", z, pc)
            dz = dz + jnp.einsum("buc,jc->buj", pc, wc)
            return dz, dwo_c

        dz, dwo_chunks = jax.lax.scan(
            chunk_step, jnp.zeros((B, U1, J), jnp.float32), (wp, valid))
        dwo = dwo + jnp.moveaxis(dwo_chunks, 0, 1).reshape(
            J, nc * chunk)[:, :V]
        cb = occb_t * gB[:, None]
        ce = occe_t * gB[:, None]
        dz = dz - cb[..., None] * w_blank - ce[..., None] * w_lab
        dwo = dwo.at[:, blank].add(-jnp.einsum("bu,buj->j", cb, z))
        dwlab = dwlab + ce[..., None] * z
        dpre = dz * (1.0 - z * z)                               # tanh'
        dzp_acc = dzp_acc + dpre
        return (dzp_acc, dwo, dwlab), dpre.sum(axis=1)

    carry0 = (jnp.zeros_like(zp, jnp.float32),
              jnp.zeros((J, V), jnp.float32),
              jnp.zeros((B, U1, J), jnp.float32))
    (dzp, dwo, dwlab), dze_rows = jax.lax.scan(
        row, carry0,
        (jnp.moveaxis(ze, 1, 0), gamma, occ_b, occ_e, logz))
    # scatter the accumulated -occ_e * z contributions at label columns
    scatter = jnp.zeros((V, J), jnp.float32).at[lab.reshape(-1)].add(
        dwlab.reshape(-1, J))
    dwo = dwo - scatter.T
    dze = jnp.moveaxis(dze_rows, 0, 1)                          # (B,T,J)

    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (dze.astype(ze.dtype), dzp.astype(zp.dtype),
            dwo.astype(w_out.dtype), f0(labels), f0(t_lens), f0(u_lens))


_rnnt_fused.defvjp(_rnnt_fused_fwd, _rnnt_fused_bwd)


def rnnt_loss_fused(
    ze: jax.Array,            # (B, T, J) encoder-side joint projection
    zp: jax.Array,            # (B, U+1, J) prediction-side joint projection
    w_out: jax.Array,         # (J, V) joint output head
    labels: jax.Array,        # (B, U) int32
    t_lens: jax.Array,        # (B,)
    u_lens: jax.Array,        # (B,)
    blank: int = 0,
    vocab_chunk: int = 0,
    lattice_impl: str = "auto",
    lattice_part=None,
) -> jax.Array:
    """Per-example RNN-T NLL from the joint *factors* — the fused,
    memory-lean equivalent of
    ``rnnt_loss_from_logits(tanh(ze[:,:,None]+zp[:,None]) @ w_out, ...)``.

    The ``(B, T, U+1, V)`` joint is never materialized, forward or
    backward: ``vocab_chunk`` bounds the live logits row at
    ``O(B·U·vocab_chunk)`` (``<= 0`` means one chunk of the full vocab),
    and gradients are analytic (``jax.custom_vjp``) so the row scan
    leaves no autodiff residuals.  ``lattice_impl`` selects the lattice
    backend (``auto`` | ``ref`` | ``pallas`` | ``interpret``);
    ``lattice_part`` is the ``(mesh, batch axes)`` a Pallas lattice runs
    under on a mesh (``Sharder.batch_partition``).
    """
    return _rnnt_fused(int(blank), int(vocab_chunk), str(lattice_impl),
                       lattice_part,
                       ze.astype(jnp.float32), zp.astype(jnp.float32),
                       w_out.astype(jnp.float32), labels,
                       t_lens.astype(jnp.int32), u_lens.astype(jnp.int32))
