"""flash_decode (kernels/flash_decode.py): one query token of a row against
its ``ctx`` visible keys and values (prefix slots included), per layer.
Cache slots beyond the row's length are not counted."""


def cost(ctx: int, n_heads: int, n_kv: int, head_dim: int,
         itemsize: int = 2) -> tuple[float, float]:
    flops = 4.0 * n_heads * head_dim * ctx
    nbytes = itemsize * head_dim * (2 * ctx * n_kv + 2 * n_heads)
    return flops, nbytes
