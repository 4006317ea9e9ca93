"""Operations and bytes that one decode step of grouped-query attention
needs for one sequence with ``ctx`` live positions (the new token's
included): QK and PV over those positions for every query head; the K
and V of those positions read once, plus q and the output.
"""


def ops_bytes(ctx: int, *, n_heads: int, n_kv_heads: int, head_dim: int,
              itemsize: int = 2) -> tuple:
    ops = 4.0 * n_heads * head_dim * ctx
    nbytes = (2.0 * ctx * n_kv_heads * head_dim * itemsize
              + 2.0 * n_heads * head_dim * itemsize)
    return ops, nbytes
