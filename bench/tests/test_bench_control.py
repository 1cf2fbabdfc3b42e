"""The controls come out not correct: the plain reference put in the
program's place one precision lower (bfloat16 for the float32 ops,
float8 for the bfloat16 model) reads above each cell's limit, where the
program reads below it.  Tiny cells on the CPU; the same runs at the
cells' own sizes on the chip set the limits (see PERF.md)."""
import pytest

import bench_testkit as kit


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return kit.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", ["ops.scan", "ops.fft_pcr",
                                  "serve.tiny.chat", "serve.tiny.gen"])
def test_control_fails_where_the_program_passes(root, cell):
    result, outcome = kit.run_cell(root, cell, seed=2 ** 31 + 99,
                                   seconds=0.3, control=True)
    assert result["correct"] is True
    assert set(outcome.controls) == set(result["check"])
    # the program passes every number; the control fails at least one
    assert all(c["value"] <= c["limit"] for c in result["check"].values())
    assert any(outcome.controls[name] > c["limit"]
               for name, c in result["check"].items())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "check"
