"""The benchmark's own tests run on the CPU at tiny sizes; they import the
harness from ``bench/`` and the program from ``src/``."""
import importlib.util
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


@pytest.fixture(autouse=True)
def _drop_compile_caches():
    """Drop jit caches and collect garbage after each test: compiled
    programs piling up in one worker have crashed the CPU backend late in
    a run (the repository's own tests drop them per module)."""
    yield
    import gc

    import jax
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="session")
def bench_run():
    """``bench/run.py`` as a module."""
    spec = importlib.util.spec_from_file_location(
        "bench_run_module", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
