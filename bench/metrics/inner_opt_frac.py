"""Device self time of the inner optimizer (Muon with its Newton-Schulz and
AdamW leaves, or AdamW): the operations whose scoped path holds
``repro.inner_opt``, over the window. None where no operation carries the
scope."""

SCOPE = "repro.inner_opt"


def read(ctx):
    from bench.scopes import scope_share

    return scope_share(ctx, SCOPE)
