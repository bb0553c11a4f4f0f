"""Fixtures of the benchmark's tests: the cells of BENCHMARK.json cut to
a size the CPU runs in seconds (the same code paths, smaller data)."""
from __future__ import annotations

import copy

import pytest


@pytest.fixture(scope="module")
def cpu_devices():
    import jax
    return jax.devices()


@pytest.fixture
def tiny_cell():
    from bench import run
    spec = run.load_spec()

    def make(name: str) -> dict:
        cell = copy.deepcopy(run.resolve(spec, name))
        cfg, traffic = cell["config"], cell["traffic"]
        if cfg["data"]["generator"] == "sparse":
            cfg["data"].update(s=3000, n=800, nnz_per_col=20)
        else:
            cfg["data"].update(s_generated=600, s=480, n=500)
        if "family" in cfg:
            cfg["family"].update(support_min=10, support_max=80)
            cfg["pool_rows"] = 256
        if traffic["kind"] == "solve":
            traffic["solver_args"] = ["--P", "32"]
            traffic["max_outer"] = 200
        else:
            traffic["rate_rps"] = 200
            traffic["answer_wait_s"] = 20
        return cell

    return make
