"""Test plumbing for every test directory, loaded before any of their own
conftest files: the scoring mix's tiny stand-in joins the benchmark test
kit's tiny cells (``bench/tests/granite_testkit.py``), so the tests that
run one tiny cell per traffic mix of ``BENCHMARK.json`` find one for it."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "bench", "tests"))

import granite_testkit  # noqa: E402

granite_testkit.extend()
