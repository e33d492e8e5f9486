"""Model step: share of the HFSL round program's device-busy time spent
under the ``lm_head`` scope (final norm, unembedding over the whole
vocabulary and the loss, forward and backward), over that program's busy
time."""
from bench import scopes


def read(ctx):
    ev = scopes.load(ctx)
    return None if ev is None else scopes.share(ev, "hfsl_round",
                                                scopes.is_lm_head)
