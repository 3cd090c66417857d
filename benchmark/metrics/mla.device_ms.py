"""Device milliseconds per step of ops under the ``mla`` scope
(the latent-attention block: norm, latent projections, head norms, rotary term, causal scores, gate, residual; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "mla")
