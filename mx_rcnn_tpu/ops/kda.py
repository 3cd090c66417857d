"""Chunked gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692) in plain ``jnp``/``lax``; autodiff gives the backward.

The recurrence, per head with state ``S`` of shape (K, V):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``g_t`` (K,) the position's log-decay of every key channel, ``<= 0``.
It is computed in chunks of ``chunk`` positions by the paper's WY form.
With ``G_i`` the running sum of ``g`` inside a chunk and ``S_0`` the state
entering it, the pseudo-values ``u_j = beta_j (v_j - (Diag(exp g_j)
S_{j-1})^T k_j)`` solve a unit-lower-triangular system a chunk and head,

    (I + A_kk) U = beta V - (beta K exp G) S_0,
    A_kk[i, j] = beta_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])   (j < i)

so with ``T = (I + A_kk)^-1``, ``U_0 = T beta V`` and ``W = T (beta K exp
G)``: ``U = U_0 - W S_0``; the outputs are ``O = (Q exp G) S_0 + A_qk U``
(``A_qk`` the same sums with ``q_i``, ``j <= i``) and the state leaving the
chunk is ``Diag(exp G_L) S_0 + (K exp(G_L - G))^T U``.  Only ``U`` and the
state need the chunks in order: a ``lax.scan`` of two small products a
chunk carries the state in float32 and emits the state entering each chunk.

**Every decay is formed as ``exp(G_a - G_b)`` over a stretch of positions
that float32 holds.**  Over a whole chunk the sum can reach ``chunk *
lower bound`` (64 positions at -5 is -320: ``exp(+320)`` is no float32), so
``exp(-G_j)`` is never formed against the chunk's start.  A chunk is cut in
sub-chunks of ``sub`` positions, and a row's factor is ``exp(G_i - M_i)``
with ``M_i`` the sum at the middle of ``i``'s sub-chunk.  A key of an
earlier sub-chunk has the factor ``exp(M_i - G_j)``: the exponent is ``<=
0``, an underflow is a true zero.  A key of the same sub-chunk has the same
factor, now of either sign.  The row's and this factor lie within ``exp(+- sub / 2
* |lower bound|)``: the caller's gate is bounded below (``kda_safe_gate``,
-5 a position), and ``8 * 5 = 40`` keeps them, the masked products above
the diagonal and their cotangents times either factor far inside float32
and bfloat16 alike (against the sub-chunk's start the factors would reach
``exp(-80)``, and a cotangent times that is flushed to zero before the
``exp(+80)`` restores it).  That is what the bound is for: the scores of a
sub-chunk are then a matrix product.

Everything that holds a decay is float32; the matrix products take their
operands in ``q.dtype`` (bfloat16 on the chip) and accumulate in float32.
On a TPU the two score matrices are one Mosaic kernel with a hand-written
backward (``kda_pallas.scores``), the factors above made in VMEM and only
``A_qk`` and ``A_kk`` written; elsewhere the products below, with
autodiff's backward.  The triangular inverse is float32 throughout: on a
TPU forward substitution on the vector unit with the chunk-heads in the
lanes (the Mosaic kernel of ``ops/kda_pallas.py``, whose backward is ``-T^T
dT T^T``, two float32 products at the highest precision), elsewhere
products at the highest precision with autodiff's backward
(``inv_unit_lower``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.ops import kda_pallas

SUB = 16
_HI = jax.lax.Precision.HIGHEST


def _kernel_takes(fits: bool, interpret: bool) -> bool:
    """A TPU (or the interpreter) and a shape the kernel ``fits``: platform
    and shape are read at trace time."""
    return fits and (interpret or jax.default_backend() == "tpu")


def inv_unit_lower(m, interpret: bool = False):
    """Inverse of unit-lower-triangular matrices (..., n, n), float32.  One
    result, two ways to it, chosen at trace time from the platform and
    ``n``: on a TPU (``interpret`` runs the kernel in the Pallas interpreter
    instead: what a test passes, on any platform), for an ``n`` that is a
    multiple of 8, row-by-row substitution with the matrices in the lanes
    (``kda_pallas.inv_unit_lower``); else ``inv_unit_lower_jnp``."""
    if _kernel_takes(kda_pallas.takes(m.shape[-1], m.dtype), interpret):
        return kda_pallas.inv_unit_lower(m, interpret)
    return inv_unit_lower_jnp(m)


def inv_unit_lower_jnp(m):
    """The inverse as matrix products, for any ``n``: blocks of 16 by the
    finite Neumann product ``(I - A)(I + A^2)(I + A^4)(I + A^8)`` (``A``
    strictly lower, ``A^16 = 0``), larger ones by halves, ``[[a, 0], [c,
    d]]^-1 = [[a^-1, 0], [-d^-1 c a^-1, d^-1]]``, so that no power beyond
    the fifteenth of a block is ever formed.  Float32 at the highest
    precision (six passes of a TPU's MXU on blocks that fill an eighth of a
    lane tile: 1.8 ms for the 2048 matrices of a part, PERF.md section 6,
    PR 39); autodiff is its backward."""
    n = m.shape[-1]
    if n <= 16:
        eye = jnp.eye(n, dtype=m.dtype)
        a = m - eye
        inv, power = eye - a, a
        for _ in range(max(n - 1, 1).bit_length() - 1):
            power = jnp.matmul(power, power, precision=_HI)
            inv = inv + jnp.matmul(inv, power, precision=_HI)
        return inv
    h = n // 2
    a = inv_unit_lower_jnp(m[..., :h, :h])
    d = inv_unit_lower_jnp(m[..., h:, h:])
    c = -jnp.matmul(jnp.matmul(d, m[..., h:, :h], precision=_HI), a,
                    precision=_HI)
    top = jnp.concatenate([a, jnp.zeros_like(m[..., :h, h:])], -1)
    return jnp.concatenate([top, jnp.concatenate([c, d], -1)], -2)


def _scores_jnp(q6, k6, gs, before, sub):
    """(A_qk, A_kk) (B, NC, H, L, L) float32 as matrix products of
    ``jnp``: q6, k6 (B, NC, H, n, c, K) cut in sub-chunks, gs their running
    log-decay inside each, before the sum ahead of each (B, NC, H, n, K)."""
    b, nc, h, n, _, dk = q6.shape
    chunk, f32, dtype = n * sub, jnp.float32, q6.dtype
    # a sub-chunk's rows against its middle, M = G - R there; every key
    # of the chunk against the middle of each sub-chunk at or after its
    # own: exp(M_i - (G_j - R_i)) inside sub-chunk i, exp((R_i + M_i) -
    # G_j) <= 1 before it, 0 after it
    mid = gs[..., sub // 2:sub // 2 + 1, :]
    q_s = (q6 * jnp.exp(gs - mid)).astype(dtype)
    k_s = (k6 * jnp.exp(gs - mid)).astype(dtype)
    at, of = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    lead = ((before + mid[..., 0, :])[..., :, None, :]
            - before[..., None, :, :])                   # (..., i, j, K)
    lead = jnp.where((at == of)[..., None], mid, lead)
    lead = jnp.where((at >= of)[..., None], lead, -jnp.inf)
    keys = (k6[..., None, :, :, :] * jnp.exp(
        lead[..., None, :] - gs[..., None, :, :, :])).astype(dtype)
    keys = keys.reshape(b, nc, h, n, chunk, dk)          # (..., i, L, K)

    def scores(rows):
        """(B, NC, H, L, L): sum_d rows_i k_j exp(G_i - G_j) for every
        (i, j) with j's sub-chunk not after i's; the caller masks."""
        return jnp.einsum("...icd,...ild->...icl", rows, keys,
                          preferred_element_type=f32).reshape(
                              b, nc, h, chunk, chunk)

    pos = jnp.arange(chunk)
    a_qk = jnp.where(pos[:, None] >= pos[None, :], scores(q_s), 0.0)
    a_kk = jnp.where(pos[:, None] > pos[None, :], scores(k_s), 0.0)
    return a_qk, a_kk


def _within_chunks(q, k, v, g, beta, chunk: int, sub: int, interpret: bool):
    """Everything of the rule that needs no other chunk, for a batch of
    sequences: (A_qk (B, NC, H, L, L), W (B, NC, H, L, K), U_0 float32
    (B, NC, H, L, V), K exp(G_L - G), Q exp G, exp G_L (B, NC, H, K) float32,
    min G_L), the products' operands in ``q.dtype``."""
    b, s, h, dk = q.shape
    nc, n, f32, dtype = s // chunk, chunk // sub, jnp.float32, q.dtype

    def cut(t):     # (B, S, H, D) -> (B, NC, H, n, c, D): heads major
        return t.reshape(b, nc, n, sub, h, t.shape[-1]).transpose(
            0, 1, 4, 2, 3, 5)

    def flat(t):    # (B, NC, H, n, c, D) -> (B, NC, H, L, D)
        return t.reshape(b, nc, h, chunk, t.shape[-1])

    q6, k6, v6 = cut(q), cut(k), cut(v)
    beta_l = flat(cut(beta.astype(f32)[..., None]))          # (..., L, 1)
    # G_i - R_i: the running sum inside a sub-chunk, in [-sub * bound, 0]
    gs = jnp.cumsum(cut(g.astype(f32)), axis=4)
    total = gs[..., -1, :]                                   # (B,NC,H,n,K)
    before = jnp.cumsum(total, axis=3) - total               # R of a sub-chunk
    g_in = flat(gs + before[..., None, :])                   # G, from the chunk's start
    g_end = (before + total)[..., -1:, :]                    # G_L (B,NC,H,1,K)

    with jax.named_scope("kda_scores"):
        if _kernel_takes(kda_pallas.scores_take(chunk, sub, dk, dtype),
                         interpret):
            a_qk, a_kk = kda_pallas.scores(flat(q6), flat(k6), g_in, sub,
                                           interpret)
        else:
            a_qk, a_kk = _scores_jnp(q6, k6, gs, before, sub)

    with jax.named_scope("kda_solve"):
        t_low = inv_unit_lower(jnp.eye(chunk, dtype=f32) + beta_l * a_kk,
                               interpret).astype(dtype)
        decay_in = jnp.exp(g_in)
        w = jnp.matmul(t_low, (flat(k6) * decay_in * beta_l).astype(dtype),
                       preferred_element_type=f32).astype(dtype)
        u0 = jnp.matmul(t_low, (flat(v6) * beta_l).astype(dtype),
                        preferred_element_type=f32)
    k_d = (flat(k6) * jnp.exp(g_end - g_in)).astype(dtype)
    q_g = (flat(q6) * decay_in).astype(dtype)
    return (a_qk.astype(dtype), w, u0, k_d, q_g, jnp.exp(g_end[..., 0, :]),
            jnp.min(g_end))


def kda_chunked(q, k, v, g, beta, chunk: int = 64, sub: int = SUB,
                interpret: bool = False):
    """q, k (B, S, H, K) as the rule takes them (normalised, ``q`` scaled);
    v (B, S, H, V); g (B, S, H, K) float32 log-decay in ``[-80 / sub, 0]``;
    beta (B, S, H) float32.  Returns (o (B, S, H, V) in ``q.dtype``, the
    most negative within-chunk cumulative log-decay, a float32 scalar
    without gradient).  ``S`` must be a multiple of ``chunk`` and ``chunk``
    of ``sub`` (a chunk shorter than ``sub`` is one sub-chunk).  ``interpret``
    runs the kernels in the Pallas interpreter, as ``inv_unit_lower``'s.

    Its float32 intermediates are a dozen arrays of the size of ``g``: a
    caller short of memory hands over one sequence at a time
    (``models/ling_flash.py``'s mixer does)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    sub = min(sub, chunk)
    nc, f32, dtype = s // chunk, jnp.float32, q.dtype
    if nc * chunk != s or chunk % sub:
        raise ValueError(f"sequence {s} / chunk {chunk} / sub-chunk {sub} "
                         "do not divide")
    a_qk, w, u0, k_d, q_g, chunk_decay, g_min = _within_chunks(
        q, k, v, g, beta, chunk, sub, interpret)

    with jax.named_scope("kda_states"):
        def carry(state, inp):
            w_c, u0_c, kd_c, dec = inp
            u = u0_c - jnp.matmul(w_c, state.astype(dtype),
                                  preferred_element_type=f32)
            out = state * dec[..., None] + jnp.einsum(
                "bhld,bhlv->bhdv", kd_c, u.astype(dtype),
                preferred_element_type=f32)
            return out, (state.astype(dtype), u.astype(dtype))

        major = lambda t: jnp.moveaxis(t, 1, 0)              # noqa: E731
        _, (entering, u) = jax.lax.scan(
            carry, jnp.zeros((b, h, dk, dv), f32),
            (major(w), major(u0), major(k_d), major(chunk_decay)))

    with jax.named_scope("kda_output"):
        o = jnp.einsum("bchld,cbhdv->bchlv", q_g, entering,
                       preferred_element_type=f32)
        o = o + jnp.einsum("bchlm,cbhmv->bchlv", a_qk, u,
                           preferred_element_type=f32)
    o = o.transpose(0, 1, 3, 2, 4).reshape(b, s, h, dv).astype(dtype)
    return o, jax.lax.stop_gradient(g_min)
