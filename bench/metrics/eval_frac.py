"""Device self time of the in-program eval: the operations whose scoped path
holds ``repro.eval``, over the window. None where no operation carries the
scope."""

SCOPE = "repro.eval"


def read(ctx):
    from bench.scopes import scope_share

    return scope_share(ctx, SCOPE)
