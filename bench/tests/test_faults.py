"""A run with the timed path broken underneath must come out not correct.

Each test drives ``run.main`` past its look for a chip, on the CPU, with
each cell's configuration replaced by a two-layer stand-in of the same
layout and its traffic by a small mix of the same kind, under the
cell's own limits, and plants one fault the cell can have:

- serving: the decode step's token altered where it is produced (the
  next id in the vocabulary, in every row);
- fine-tuning: a step that returns its state unchanged, and a step that
  leaves out half of each batch and takes the mean over the rest.

A one-chip cell has no exchange between chips to leave out.
"""
import json
import os

import pytest

import run
import serve

DATA = os.path.join(os.path.dirname(__file__), "data")
SERVE = "smollm_135m.instruct_rate"
TRAIN = "smollm_135m.finetune_512"
STANDIN = {SERVE: "tiny_chat", TRAIN: "tiny_train"}


def _bench_file(tmp_path, cell, standin):
    bench = {"traffic_dir": DATA, "limits_dir": os.path.join(run.HERE,
                                                             "limits"),
             "configs": [{"name": "tiny",
                          "file": "bench/tests/data/tiny_model.json"}],
             "workloads": [{"name": cell, "config": "tiny",
                            "traffic": standin, "chips": 1}],
             "per_layer": []}
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    return str(path)


def _result(tmp_path, capsys, cell, standin, seconds="2"):
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 9),
                   "--seconds", seconds], require_chip=False,
                  bench_file=_bench_file(tmp_path, cell, standin))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(autouse=True)
def _scratch_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


@pytest.mark.parametrize("standin", ["tiny_chat", "tiny_batch"])
def test_served_token_altered(tmp_path, capsys, monkeypatch, standin):
    build = serve.build

    def broken(m, t, seed):
        eng = build(m, t, seed)
        decode = eng._decode

        def altered(params, cache, tokens, pos):
            nxt, cache = decode(params, cache, tokens, pos)
            return (nxt + 1) % m["vocab_size"], cache
        eng._decode = altered
        return eng

    monkeypatch.setattr(serve, "build", broken)
    out = _result(tmp_path, capsys, SERVE, standin)
    assert out["correct"] is False
    assert out["checks"]["widest_gap"]["value"] > \
        out["checks"]["widest_gap"]["limit"]


def _patch_step(monkeypatch, wrap):
    import train
    build = train.build

    def broken(*a, **k):
        state, step_fn = build(*a, **k)
        return state, wrap(step_fn)

    monkeypatch.setattr(train, "build", broken)


def test_train_step_state_unchanged(tmp_path, capsys, monkeypatch):
    _patch_step(monkeypatch,
                lambda step: lambda state, b: (state, step(state, b)[1]))
    out = _result(tmp_path, capsys, TRAIN, "tiny_train")
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_train_step_half_batch(tmp_path, capsys, monkeypatch):
    def half(step):
        def broken(state, b):
            n = b["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in b.items()})
        return broken

    _patch_step(monkeypatch, half)
    out = _result(tmp_path, capsys, TRAIN, "tiny_train")
    assert out["correct"] is False
