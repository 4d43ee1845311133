"""The harness's compile setting (tests/conftest.py): most modules compile
with jax's `jax_disable_most_optimizations`; the modules that read what a
compile produced keep jax's default options, and are handed no executable
compiled under the light setting."""
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src import compiler, dispatch
from jax._src.lib import xla_client

import conftest

TESTS = pathlib.Path(__file__).resolve().parent

# compiled for a described v5e (memory fit, the tick's aliasing), a lowered
# program's sharding, memory and cost analyses, plans ranked by CPU time
NAMED = ("test_chip_compile.py", "test_auto_parallel.py",
         "test_mem_observability.py", "test_auto_parallel_planner.py")

# what a module calls when it reads a compiled executable's text, memory or
# cost
READS_A_COMPILE = re.compile(
    r"memory_analysis|cost_analysis|hlo_audit|mem_audit|\.compile\(\)"
    r"|paddle\.flops|CostModel")


def _backend_options():
    debug = compiler.get_compile_options(
        num_replicas=1, num_partitions=1).executable_build_options \
        .debug_options
    return (debug.xla_backend_optimization_level,
            debug.xla_llvm_disable_expensive_passes)


@pytest.fixture
def restore_light():
    yield
    conftest.set_compile_effort(True)       # this module's own setting


@pytest.mark.parametrize("name", sorted(conftest.DEFAULT_COMPILE_FILES)
                         + ["benchmark/test_benchmark_cells.py"])
def test_an_exempt_module_compiles_with_jax_defaults(name, restore_light):
    path = TESTS / name
    assert path.is_file(), name
    assert conftest.compile_light(TESTS / "test_compile_setting.py")
    light = _backend_options()
    conftest.set_compile_effort(conftest.compile_light(path))
    fresh = xla_client.CompileOptions().executable_build_options \
        .debug_options
    assert _backend_options() == (fresh.xla_backend_optimization_level,
                                  fresh.xla_llvm_disable_expensive_passes)
    assert _backend_options() != light


def test_the_named_modules_and_every_reader_of_a_compile_are_exempt():
    assert set(NAMED) <= conftest.DEFAULT_COMPILE_FILES
    readers = {p.name for p in TESTS.glob("test_*.py")
               if p.name != "test_compile_setting.py"
               and READS_A_COMPILE.search(p.read_text())}
    assert readers and readers <= conftest.DEFAULT_COMPILE_FILES, \
        readers - conftest.DEFAULT_COMPILE_FILES


def test_no_light_executable_reaches_a_default_module(restore_light):
    compiles = []

    def count(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            compiles.append(event)
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.arange(5.0)
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        conftest.set_compile_effort(True)
        f(x), f(x)
        assert len(compiles) == 1            # reused within the setting
        conftest.set_compile_effort(False)
        f(x)
        assert len(compiles) == 2            # compiled anew, by default
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
