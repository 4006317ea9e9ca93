"""The lower-precision control, put in the program's place, must come
out not correct under each cell's limits.

The control is the plain reference at float8 (``reference.py``'s
``fp8``): its readings are taken on the same prompts and served tokens,
or on the same batches, as the program's in one run of a two-layer
stand-in of the cell's configuration on the CPU, and judged as if they
were the program's.  The same readings at the cells' own sizes on the
chip are in PERF.md."""
import os

import pytest

import run
from test_faults import DATA, STANDIN, _scratch_cache  # noqa: F401


def _context(cell):
    model = run.load_json(DATA, "tiny_model.json")["model"]
    spec = run.load_json(DATA, STANDIN[cell] + ".json")
    ctx = run.Context({"name": cell, "chips": 1}, model, spec,
                      2 ** 32 + 17, 2.0, False)
    ctx.controls = ("fp8",)
    return ctx


@pytest.mark.parametrize("cell", sorted(STANDIN))
def test_fp8_control_is_not_correct(cell):
    import importlib
    ctx = _context(cell)
    driver = importlib.import_module(ctx.traffic["driver"])
    numbers = driver.run(ctx)["numbers"]
    control = {k[:-len(".fp8")]: v for k, v in numbers.items()
               if k.endswith(".fp8")}
    sound = {k: v for k, v in numbers.items() if "." not in k}
    limits = os.path.join(run.HERE, "limits")
    assert set(control) == set(sound)
    assert not all(ok for *_, ok in run.judge(limits, cell, control))
