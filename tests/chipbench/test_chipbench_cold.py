"""A run whose process had to compile the step fills the compilation cache
and stops before its window; run.py then measures in a fresh child that
loads every program from the cache, so no measured process has compiled
the step."""
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import cells, harness, run  # noqa: E402
from test_chipbench_faults import BENCH, SEED, TINY  # noqa: E402


@pytest.fixture
def fresh_cache(tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    yield tmp_path
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _run(cell, stop):
    import jax

    return harness.run_cell(cell, TINY, SEED, 0.3, False,
                            jax.devices()[:1], bench=BENCH,
                            age_at_start=0.0, t_start=time.perf_counter(),
                            stop_if_compiled=stop)


def test_compiling_run_stops_and_the_next_measures(fresh_cache):
    cell, _ = cells.load_cell("mamba2_780m.full_w4_s2048")
    cell = dict(cell, seq=64, batches=8)
    assert _run(cell, stop=True) == (None, [])
    assert any(fresh_cache.iterdir())
    result, lines = _run(cell, stop=True)
    assert result is not None and result["correct"], lines


class _Child:
    def __init__(self, rc, calls, env):
        self.rc = rc
        calls.append(dict(env))

    def wait(self):
        return self.rc

    def poll(self):
        return self.rc


@pytest.mark.parametrize("rcs,want", [((0,), 0), ((1,), 1),
                                      ((run.COMPILED, 0), 0),
                                      ((run.COMPILED, 3), 3),
                                      ((run.COMPILED, run.COMPILED), 1)])
def test_launcher_runs_again_only_after_a_compiling_child(monkeypatch, rcs,
                                                          want):
    calls, rcs = [], list(rcs)
    monkeypatch.setattr(run.subprocess, "Popen", lambda cmd, env, **_:
                        _Child(rcs.pop(0), calls, env))
    monkeypatch.setattr(run.signal, "signal", lambda *_: None)
    assert run.launch(["--workload", "x"]) == want
    assert not rcs
    assert all(run.CHILD in env for env in calls)
    assert [run.CACHED in env for env in calls] == \
        [False, True][:len(calls)]
