"""flash_attention (kernels/flash_attention.py): causal prefill attention
of one row of ``P`` valid tokens over ``n_prefix`` always-visible prefix
slots and itself, per layer. Padding columns and rows are not counted."""


def cost(P: int, n_prefix: int, n_heads: int, n_kv: int, head_dim: int,
         itemsize: int = 2) -> tuple[float, float]:
    pairs = P * (P + 1) / 2 + P * n_prefix          # (query, key) pairs
    flops = 4.0 * n_heads * head_dim * pairs        # QK^T and PV
    nbytes = itemsize * head_dim * (2 * P * n_heads
                                    + 2 * (P + n_prefix) * n_kv)
    return flops, nbytes
