"""Share of its roofline that the fused SALR kernel reaches in the traced
window: the sum over its calls of the least time the chip could take
(the larger of operations over peak FLOP/s and bytes over peak
bytes/s, from ``counts/salr_spmm.py`` at the live rows of each call)
over the kernel's summed device time."""
import sys

# The trace names a Pallas kernel's op after its caller, not after the
# kernel: the fused SALR kernel runs as the op of its jitted wrapper
# ``ops.salr_matmul``.  Ops that read its output name it as an operand,
# so the match is on the op's own name.
KERNEL_OP = "salr_matmul"


def read(inp):
    s, steps = inp["trace"], inp.get("steps")
    if s is None or not steps:
        return None
    tm = inp["trace_mod"]
    t, n = tm.op_time(s, lambda name: tm.PALLAS in name
                      and tm.base_name(name) == KERNEL_OP)
    if not n:
        return None
    m, pk, counts = inp["model"], inp["peaks"], inp["counts"]
    layers = m["num_hidden_layers"]
    least = mem_bound = 0.0
    calls = [len(ctxs) for _, ctxs in steps if ctxs]
    calls += [p for prefills, _ in steps for p in prefills]
    for rows in calls:
        for k, nn in counts.linear_shapes(m):
            ops, nbytes = counts.salr_spmm.ops_bytes(
                rows, k, nn, sparsity=m["sparsity"],
                rank=m["lora_rank"] + m["res_rank"])
            tc, tm = ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
            least += layers * max(tc, tm)
            mem_bound += layers * tm * (tm >= tc)
    print(f"salr_spmm roofline: {len(calls)} calls x {layers} layers x 7 "
          f"linears; least time {least:.6f} s, of it memory-bound "
          f"{mem_bound:.6f} s; kernel {t:.6f} s in {n} ops",
          file=sys.stderr)
    return 100.0 * least / t
