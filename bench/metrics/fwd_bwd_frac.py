"""Device self time of the inner steps' forward, backward and
rematerialisation: the operations whose scoped path holds ``repro.fwd_bwd``,
over the window. None where no operation carries the scope."""

SCOPE = "repro.fwd_bwd"


def read(ctx):
    from bench.scopes import scope_share

    return scope_share(ctx, SCOPE)
