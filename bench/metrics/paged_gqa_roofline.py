"""Share of its roofline that the paged GQA decode kernel reaches in the
traced window: per decode tick and layer, the least time for all live
sequences' attention (``counts/paged_gqa.py``) over the kernel's summed
device time.

The trace names a Pallas kernel's op after its caller, not after the
kernel, and the attention kernel sits in a closed call of the layer
scan.  It is the one Pallas kernel of the serving steps that is not the
SALR linears' (``%salr_matmul``): prefill runs no other."""
import sys


def read(inp):
    s, steps, tm = inp["trace"], inp.get("steps"), inp["trace_mod"]
    if s is None or not steps:
        return None
    t, n = tm.op_time(s, lambda name: tm.PALLAS in name
                      and tm.base_name(name) != "salr_matmul")
    if not n:
        return None
    m, pk, counts = inp["model"], inp["peaks"], inp["counts"]
    least = 0.0
    for _, ctxs in steps:
        if ctxs:
            ops, nbytes = counts.decode_attention(m, ctxs)
            least += max(ops / pk["bf16_flops_per_s"],
                         nbytes / pk["hbm_bytes_per_s"])
    print(f"paged_gqa roofline: least time {least:.6f} s; kernel {t:.6f} s "
          f"in {n} ops", file=sys.stderr)
    return 100.0 * least / t
