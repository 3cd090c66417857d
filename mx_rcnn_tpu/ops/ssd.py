"""Chunked state-space scan (Mamba-2's SSD form) in plain ``jnp``/``lax``.

The recurrence, per head with state ``h`` of shape (P, N):

    h_t = exp(dt_t * a) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t . C_t

is computed in chunks of ``chunk`` positions: inside a chunk every pair
(l, s <= l) is one masked matrix product (the decay between them is
``exp(cs_l - cs_s)``, ``cs`` the running sum of ``dt * a`` in the chunk);
each chunk leaves a state, the states are carried from chunk to chunk by a
sequential ``lax.scan`` in float32, and the carried state adds its part to
every position of the next chunk.  Autodiff gives the backward pass.

Heads come in ``G`` groups that share ``B`` and ``C`` (``R = H // G`` heads
a group).  Everything that holds a decay is float32; the matrix products
take their operands in ``x.dtype`` (bfloat16 on the chip) and accumulate in
float32.  Large intermediates keep the two chunk axes minor, (..., L, L),
so that the TPU's (8, 128) tiles are full.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_scan(x, dt, a, b, c, chunk: int):
    """x (B, S, H, P); dt (B, S, H) float32, positive (after softplus);
    a (H,) float32, negative; b, c (B, S, G, N).  Returns y (B, S, H, P) in
    ``x.dtype``; ``S`` must be a multiple of ``chunk``.  The skip term
    ``D * x`` is the caller's."""
    bt, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r, nc, f32 = h // g, s // chunk, jnp.float32
    if nc * chunk != s or r * g != h:
        raise ValueError(f"sequence {s} / chunk {chunk} or heads {h} / "
                         f"groups {g} do not divide")
    dtype = x.dtype
    xc = x.reshape(bt, nc, chunk, g, r, p)
    bc = b.reshape(bt, nc, chunk, g, n)
    cc = c.reshape(bt, nc, chunk, g, n)
    # (B, nc, G, R, L): the chunk axis minor
    dtc = dt.astype(f32).reshape(bt, nc, chunk, g, r).transpose(0, 1, 3, 4, 2)
    cs = jnp.cumsum(dtc * a.astype(f32).reshape(g, r, 1), axis=-1)

    with jax.named_scope("ssd_intra"):
        # decay[l, s] = exp(cs_l - cs_s) for s <= l, else 0; the mask goes
        # on the exponent so that no masked entry overflows
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        seg = cs[..., :, None] - cs[..., None, :]
        decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
        cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                        preferred_element_type=f32)
        w = cb[:, :, :, None] * decay * dtc[..., None, :]
        y = jnp.einsum("bcgrls,bcsgrp->bclgrp", w.astype(dtype), xc,
                       preferred_element_type=f32)

    with jax.named_scope("ssd_states"):
        # what each chunk adds to the state: positions weighted by dt and
        # by the decay from them to the chunk's end
        to_end = (jnp.exp(cs[..., -1:] - cs) * dtc).transpose(0, 1, 4, 2, 3)
        xw = (xc.astype(f32) * to_end[..., None]).astype(dtype)
        states = jnp.einsum("bcsgn,bcsgrp->cbgrpn", bc, xw,
                            preferred_element_type=f32)
        chunk_decay = jnp.exp(cs[..., -1]).transpose(1, 0, 2, 3)

        def carry(h_in, inp):
            add, dec = inp
            return h_in * dec[..., None, None] + add, h_in

        _, entering = jax.lax.scan(
            carry, jnp.zeros((bt, g, r, p, n), f32), (states, chunk_decay))

    with jax.named_scope("ssd_carried"):
        # the state entering a chunk, decayed to each position, read by C
        off = jnp.einsum("bclgn,cbgrpn->bclgrp", cc, entering.astype(dtype),
                         preferred_element_type=f32)
        y = y + off * jnp.exp(cs).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(bt, s, h, p).astype(dtype)
