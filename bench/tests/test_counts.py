"""The count functions and the peak table, against hand-worked numbers."""
import pytest

import run

counts = run.load_module(run.os.path.join(run.HERE, "counts", "forward.py"),
                         "bench_counts_forward")


def test_salr_linear_smollm_mlp_up_at_64_rows():
    # K 576, N 1536, M 64, p 0.5, adapters 64 + 64
    nnz = 576 * 1536 // 2                                  # 442,368
    ops = 2 * 64 * nnz + 2 * 64 * 128 * (576 + 1536)       # 91,226,112
    nbytes = (576 * 1536 // 8 + 2 * nnz + 2 * 128 * (576 + 1536)
              + 2 * 64 * 576 + 2 * 64 * 1536)              # 1,806,336
    assert (ops, nbytes) == (91_226_112, 1_806_336)
    assert counts.salr_spmm.ops_bytes(64, 576, 1536, sparsity=0.5,
                                      rank=128) == (ops, nbytes)


def test_paged_gqa_smollm_decode_at_300_positions():
    # 9 query heads, 3 KV heads of 64, 300 live positions
    ops = 2 * 2 * 9 * 64 * 300                             # QK + PV
    nbytes = 2 * 300 * 3 * 64 * 2 + 2 * 9 * 64 * 2         # K, V; q, out
    assert (ops, nbytes) == (691_200, 232_704)
    assert counts.paged_gqa.ops_bytes(300, n_heads=9, n_kv_heads=3,
                                      head_dim=64) == (ops, nbytes)


def test_model_counts_add_up_per_layer():
    m = run.load_json(run.HERE, "configs", "smollm_135m.json")["model"]
    ops, _ = counts.linears(m, 1)
    per_layer = sum(counts.salr_spmm.ops_bytes(1, k, n, sparsity=0.5,
                                               rank=128)[0]
                    for k, n in counts.linear_shapes(m))
    assert ops == 30 * per_layer
    assert counts.decode_ops(m, [10]) == (
        ops + 30 * 4 * 9 * 64 * 10 + 2 * 576 * 49152)


def test_peaks_keyed_by_device_kind():
    v5e = run.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")
