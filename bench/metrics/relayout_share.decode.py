"""Model step: share of the decode segment programs' device-busy time
spent moving or re-laying out data rather than computing
(``scopes.is_relayout``: the ``kv_cache`` scope, the layer scan's own
slicing and write-back, the kernel wrappers' padding, XLA's copies of the
whole cache), over those programs' busy time."""
from bench import scopes


def read(ctx):
    ev = scopes.load(ctx)
    return None if ev is None else scopes.share(ev, "decode_segment",
                                                scopes.is_relayout)
