"""A whole run at tiny sizes on the CPU, past the look for a chip: sound
runs come out correct, and runs with the timed path broken underneath
(an answer altered where it is produced, half of each batch left out) come
out not correct. The command itself refuses to run without a TPU."""
import dataclasses
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import REPO
from harness.runner import run_cell
from harness.spec import Spec

CELLS = ["sift1m_l5x3_help.match.closed32", "sift1m_l5x3_pq4.match.open"]
SEED = 2**31 + 99


def _run(root, cell, monkeypatch, trace=False):
    # no persistent compile cache for these runs: JAX read this variable
    # when it was imported, so setting it now turns nothing on
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(root, "c"))
    return run_cell(Spec(root), cell, SEED, 2.0, trace,
                    t_start=time.perf_counter(), require_chips=False,
                    say=lambda s: None)


def test_command_refuses_without_a_tpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"), "--workload",
         CELLS[1], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr
    assert "{" not in p.stdout and "no chip" in p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell, monkeypatch,
                              jax_config_restored):
    out = _run(tiny_root, cell, monkeypatch)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) >= {"recall_at_10", "setup_s"}
    assert out["device"]["platform"] == "cpu"


def test_traced_run_reads_counters_and_spans(tiny_root, monkeypatch,
                                             jax_config_restored):
    out = _run(tiny_root, CELLS[0], monkeypatch, trace=True)
    assert out["correct"]
    m = out["metrics"]
    # no device plane on the CPU: the trace readers find nothing to read
    assert {"batch_fill.closed", "engine_ms_per_batch.closed",
            "fp_evals_per_query"} <= set(m)
    assert "traversal_ms_per_batch" not in m and "adc_scan_roofline" not in m
    assert 0 < m["batch_fill.closed"]["value"] <= 100
    assert m["fp_evals_per_query"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_result_cache_setting_is_applied(tiny_root, monkeypatch,
                                         jax_config_restored):
    """``serve.result_cache`` true serves repeats from the program's result
    cache (answers in bucket 0), and those answers are compared too."""
    spec = Spec(tiny_root)
    cell = spec.cell(CELLS[0])
    cfg = {**cell.config, "serve": {**cell.config["serve"],
                                    "result_cache": True}}
    monkeypatch.setattr(Spec, "cell", lambda self, name: dataclasses.replace(
        cell, config=cfg))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(tiny_root, "c"))
    said = []
    out = run_cell(spec, CELLS[0], SEED, 2.0, False,
                   t_start=time.perf_counter(), require_chips=False,
                   say=said.append)
    assert out["correct"], out["checks"]
    buckets = [s for s in said if s.startswith("answers by bucket")]
    assert buckets and "{0: " in buckets[0], said


def _altered(search):
    def broken(self, queries, params=None, *a, **kw):
        res = search(self, queries, params, *a, **kw)
        ids = np.asarray(res.ids)
        n = self.n_items
        return res._replace(ids=np.where(ids >= 0, (ids + 1) % n, ids))
    return broken


def _half_left_out(search):
    def broken(self, queries, params=None, *a, **kw):
        res = search(self, queries, params, *a, **kw)
        ids = np.asarray(res.ids).copy()
        ids[1::2] = -1  # every second row of the batch gets nothing
        return res._replace(ids=ids)
    return broken


@pytest.mark.parametrize("fault", [_altered, _half_left_out],
                         ids=["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root, cell, fault,
                                          monkeypatch, jax_config_restored):
    from repro.api import Engine

    monkeypatch.setattr(Engine, "search", fault(Engine.search))
    out = _run(tiny_root, cell, monkeypatch)
    assert not out["correct"], out["checks"]
