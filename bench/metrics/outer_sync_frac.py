"""Device self time of the outer sync (pseudogradient, compress/EF, reduce,
outer update and worker reset): the operations whose scoped path holds
``repro.outer_sync``, over the window. None where no operation carries the
scope."""

SCOPE = "repro.outer_sync"


def read(ctx):
    from bench.scopes import scope_share

    return scope_share(ctx, SCOPE)
