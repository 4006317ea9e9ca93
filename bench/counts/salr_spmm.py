"""Operations and bytes that one SALR linear needs:
y (M, N) = x (M, K) @ (mask * W) + (x @ A_cat) @ B_cat.

They come from the shapes and the configured sparsity, never from the
program's arrays: nnz = (1 - p) K N kept weights; a bitmap of K N bits;
the kept values at the base dtype; the adapters at their physical rank
R; x and y once each.  Rows the algorithm does not need (idle slots,
bucket padding) are not counted: M is the live rows.
"""


def ops_bytes(m: int, k: int, n: int, *, sparsity: float, rank: int,
              itemsize: int = 2) -> tuple:
    nnz = (1.0 - sparsity) * k * n
    ops = 2.0 * m * nnz + 2.0 * m * rank * (k + n)
    nbytes = (k * n / 8.0 + nnz * itemsize + rank * (k + n) * itemsize
              + m * k * itemsize + m * n * itemsize)
    return ops, nbytes
