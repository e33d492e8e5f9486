"""lora_matmul (kernels/lora_matmul.py). Forward ``x @ W + s (x @ A) @ B``
for ``M`` rows (the backward's ``dx`` is the same kernel with the
transposed weights), and the adapter-gradient kernel ``dA, dB`` from
``x`` and ``dy``."""


def forward(M: int, K: int, N: int, rank: int,
            itemsize: int = 2) -> tuple[float, float]:
    flops = 2.0 * M * K * N + 2.0 * M * rank * (K + N)
    nbytes = itemsize * (M * K + K * N + M * N + rank * (K + N))
    return flops, nbytes


def backward(M: int, K: int, N: int, rank: int,
             itemsize: int = 2) -> tuple[float, float]:
    flops = 4.0 * M * rank * (K + N)
    nbytes = itemsize * (M * K + M * N + 2 * rank * (K + N))
    return flops, nbytes
