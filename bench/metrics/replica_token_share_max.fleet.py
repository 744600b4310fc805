"""replica_token_share_max.fleet: the largest replica's share of the
tokens released in the window, times the number of replicas (1.0 is an
even spread), from the fleet's record of which replica served each
released request."""


def read(ctx):
    tokens = ctx["counters"]["replica_tokens"]
    total = sum(tokens)
    if len(tokens) < 2 or total <= 0:
        return None
    return max(tokens) / total * len(tokens)
