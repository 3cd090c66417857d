"""Device milliseconds per step of ops under the ``dsa_index_loss`` scope
(the indexer's loss: the target (the attention's probabilities summed over heads), the KL divergence and its gradient with respect to the indexer's operands)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "dsa_index_loss")
