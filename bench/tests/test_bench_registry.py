"""The repository's own ``BENCHMARK.json`` is runnable by name: every
file a cell, a configuration or a metric needs is where the harness looks
for it, and every end-to-end metric scoped to a cell is one that the
cell's driver returns (checked on the tiny stand-in cell of the same
traffic, on the CPU)."""
import json
import os

import pytest

import bench_testkit as kit
from harness import traffic
from harness.cells import load_cell, load_module

with open(os.path.join(kit.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
# the tiny stand-in of each traffic mix
TINY = {mix: cell for cell, _, mix in kit.CELLS}
SEED, SECONDS = 2 ** 31 + 7, 1.0


def _scoped(metrics, cell):
    return {m["name"] for m in metrics
            if "workloads" not in m or cell in m["workloads"]}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return kit.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="module")
def tiny_runs(root):
    """One run of each tiny cell, made once for the module."""
    cache = {}

    def run(cell):
        if cell not in cache:
            cache[cell] = kit.run_cell(root, cell, seed=SEED,
                                       seconds=SECONDS)
        return cache[cell]
    return run


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_exist(cell):
    entry = CELLS[cell]
    bench_dir = os.path.join(kit.ROOT, BENCH["paths"][0])
    assert entry["config"] in CONFIGS
    assert os.path.isfile(os.path.join(kit.ROOT,
                                       CONFIGS[entry["config"]]["file"]))
    for kind, name in (("traffic", entry["traffic"]), ("workloads", cell)):
        assert os.path.isfile(os.path.join(bench_dir, kind, f"{name}.json"))
    loaded = load_cell(cell, kit.ROOT)
    assert loaded.driver.run
    assert loaded.settings["limits"]
    assert loaded.module("reference", loaded.config["reference"])
    assert _scoped(BENCH["end_to_end"], cell) >= {"setup_s"}
    assert len(_scoped(BENCH["end_to_end"], cell)) >= 2
    assert _scoped(BENCH["per_layer"], cell)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader_and_real_cells(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert callable(load_module(kit.BENCH_DIR, "metrics", metric).read)
    assert entry["workloads"] and set(entry["workloads"]) <= set(CELLS)
    moves = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    for cell in entry["workloads"]:
        assert moves["name"] in _scoped(BENCH["end_to_end"], cell)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_driver_returns_its_end_to_end_metrics(cell, tiny_runs):
    tiny = TINY[CELLS[cell]["traffic"]]
    _, outcome = tiny_runs(tiny)
    returned = set(outcome.end_to_end) | {"setup_s"}
    assert _scoped(BENCH["end_to_end"], cell) <= returned
    for name in _scoped(BENCH["end_to_end"], cell) - {"setup_s"}:
        assert outcome.end_to_end[name] > 0


def test_serving_stand_ins_return_the_serving_metrics(tiny_runs):
    """The serving driver returns the serving metrics, whichever of them
    ``BENCHMARK.json`` registers."""
    for tiny in ("serve.tiny.chat", "serve.tiny.gen"):
        result, outcome = tiny_runs(tiny)
        names = ("ttft_p95_ms", "itl_p95_ms", "tokens_per_s")
        assert set(names) <= set(outcome.end_to_end)
        assert all(0 < outcome.end_to_end[n] < float("inf") for n in names)
        assert "setup_s" in result["metrics"]
        assert result["correct"] is True


def test_queue_wait_read_on_the_tiny_chat_cell(root, tiny_runs):
    _, outcome = tiny_runs("serve.tiny.chat")
    waits = outcome.readings["queue_wait_s"]
    assert waits and all(w >= 0 for w in waits)
    # one wait for each request due in the window: every one was admitted
    assert len(waits) == outcome.attempted
    cell = load_cell("serve.tiny.chat", root)
    ctx = type("C", (), {"readings": outcome.readings, "cell": cell})()
    value = cell.module("metrics", "queue_wait_p95_ms.chat").read(ctx)
    assert value is not None and value >= 0
    assert min(waits) * 1e3 <= value <= max(waits) * 1e3


def test_every_request_due_in_the_window_is_attempted(root, tiny_runs):
    """An open loop counts every request scheduled inside the window, also
    one that fell due while the engine's last call ran."""
    _, outcome = tiny_runs("serve.tiny.chat")
    cell = load_cell("serve.tiny.chat", root)
    due = traffic.open_loop(cell.traffic, cell.settings["rate"], SECONDS,
                            cell.config["vocab_size"], SEED)
    assert outcome.attempted == len(due) > 0
    assert outcome.failed == 0
