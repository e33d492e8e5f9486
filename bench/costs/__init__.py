"""Operations and bytes each kernel (and the whole model step) needs,
counted from shapes: the least work the algorithm does, never what a
particular implementation happens to do (padding, recomputation and
blocks that read beyond a row's length are not counted)."""


def bound_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time on the chip and the side that bounds it."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
