"""Scheduler: share of executed decode slot-steps that served no token
(retired or empty slots riding along), from ``EngineStats``."""


def read(ctx):
    st = ctx.result["stats"]
    total = st.tokens + st.padded_tokens
    return 100.0 * st.padded_tokens / total if total else None
