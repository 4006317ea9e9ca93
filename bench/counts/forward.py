"""Operations the model's steps need, from the configuration's shapes:
the SALR linears (``salr_spmm.py``), attention over the live positions,
and the dense LM head.  Embedding lookups, norms and activations are
left out (they are not matrix work).

- prefill of P live prompt tokens: the linears at M = P, causal
  attention 2 H hd P (P + 1) per layer, the head on the last token;
- decode tick over sequences with live contexts c_i: the linears at
  M = (number of sequences), attention 4 H hd c_i per layer, the head
  on every row;
- training step over B sequences of S tokens: the linears forward
  (2 nnz + 2 R (K + N) per token) and backward without a weight
  gradient for the frozen base (dx: the same again; the adapters'
  gradients: 2 R (K + N)); causal attention 2 H hd S (S + 1) per layer
  forward and twice that backward; the head forward and dx
  (4 d V per token).  Recomputation is not counted.
"""
import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_counts_{name}", os.path.join(_HERE, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


salr_spmm = _load("salr_spmm")
paged_gqa = _load("paged_gqa")


def linear_shapes(m: dict) -> list:
    d, hd = m["d_model"], m["head_dim"]
    q, kv, f = m["n_heads"] * hd, m["n_kv_heads"] * hd, m["d_ff"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


def _rank(m):
    return m["lora_rank"] + m["res_rank"]


def linears(m: dict, rows: int) -> tuple:
    """(ops, bytes) of every SALR linear of the model at M = rows."""
    ops = nbytes = 0.0
    for k, n in linear_shapes(m):
        o, b = salr_spmm.ops_bytes(rows, k, n, sparsity=m["sparsity"],
                                   rank=_rank(m))
        ops, nbytes = ops + o, nbytes + b
    layers = m["num_hidden_layers"]
    return ops * layers, nbytes * layers


def decode_attention(m: dict, ctxs) -> tuple:
    ops = nbytes = 0.0
    for c in ctxs:
        o, b = paged_gqa.ops_bytes(c, n_heads=m["n_heads"],
                                   n_kv_heads=m["n_kv_heads"],
                                   head_dim=m["head_dim"])
        ops, nbytes = ops + o, nbytes + b
    layers = m["num_hidden_layers"]
    return ops * layers, nbytes * layers


def head_ops(m: dict, rows: int) -> float:
    return 2.0 * rows * m["d_model"] * m["vocab_size"]


def prefill_ops(m: dict, p: int) -> float:
    attn = 2.0 * m["n_heads"] * m["head_dim"] * p * (p + 1)
    return (linears(m, p)[0] + attn * m["num_hidden_layers"]
            + head_ops(m, 1))


def decode_ops(m: dict, ctxs) -> float:
    rows = len(ctxs)
    return (linears(m, rows)[0] + decode_attention(m, ctxs)[0]
            + head_ops(m, rows))


def train_step_ops(m: dict, batch: int, seq: int) -> float:
    tokens = batch * seq
    per_token = 0.0
    for k, n in linear_shapes(m):
        nnz = (1.0 - m["sparsity"]) * k * n
        per_token += 4.0 * nnz + 6.0 * _rank(m) * (k + n)
    per_token *= m["num_hidden_layers"]
    per_token += 4.0 * m["d_model"] * m["vocab_size"]
    attn = 3 * 2.0 * m["n_heads"] * m["head_dim"] * seq * (seq + 1)
    return per_token * tokens + attn * batch * m["num_hidden_layers"]
