"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip (the tiny cells run on the
CPU) and plants one fault in the program (``harness/faults.py``), where
the cell could have it: an answer or a token altered where it is
produced, half of the batch left out, a step that returns its state
unchanged."""
import pytest

import bench_testkit as kit
from harness.faults import planted


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return kit.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell,fault", [
    ("ops.scan", "answer"), ("ops.fft_pcr", "answer"),
    ("ops.scan", "half_batch"), ("ops.fft_pcr", "half_batch"),
    ("serve.tiny.chat", "token"), ("serve.tiny.chat", "state"),
    ("serve.tiny.gen", "token"), ("serve.tiny.gen", "state")])
def test_planted_fault_is_not_correct(root, cell, fault):
    with planted(fault):
        result, _ = kit.run_cell(root, cell, seconds=0.3)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["check"].values())
    # the same cell without the fault is correct
    if fault in ("answer", "state"):
        assert kit.run_cell(root, cell, seconds=0.3)[0]["correct"] is True
