"""CPU rehearsal of ``chip_smoke.py``: its phases at reduced widths, the
backend's warm-up contract, the compile cache's placement, and the
script's refusal to run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402
from repro.configs import ARCHS  # noqa: E402
from repro.core.milp import Planner  # noqa: E402
from repro.core.profiler import Profiler  # noqa: E402
from repro.runtime.backend import (EngineBackend,  # noqa: E402
                                   enable_compile_cache)
from repro.runtime.metrics import Server  # noqa: E402


@pytest.fixture(scope="module")
def reduced_backend():
    """The smoke's backend at reduced float32 widths (full_width off)."""
    return EngineBackend(max_batch=4, max_seq=32, prompt_len=8, max_new=4)


def test_serve_phase_reduced(reduced_backend):
    out = chip_smoke.serve_phase(reduced_backend, n_requests=8)
    assert out["ok"] == out["deadline_met"] == out["completions"] == 8
    assert out["dropped"] == 0 and out["compiles_serving"] == 0
    # the warm-up compiled prefill and decode for every batch size
    assert out["compiles_setup"] >= 2 * reduced_backend.max_batch
    assert {t for t, _, _ in out["servers"]} == {"classify", "caption"}


def test_consistency_phase_reduced(reduced_backend):
    eng = reduced_backend.engine_for(chip_smoke.ARCH)
    out = chip_smoke.consistency_phase(eng, reduced_backend.prompt_len)
    # float32 at reduced widths: the paths agree far inside the bf16 bound
    assert max(out["prefill"], out["decode"]) < 1e-4 * out["scale"]


def test_consistency_phase_catches_a_wrong_cache(reduced_backend):
    """A decode that reads another prompt's cache fails the check."""
    eng = reduced_backend.engine_for(chip_smoke.ARCH)

    class OtherCache:
        def __init__(self):
            self.model, self.params = eng.model, eng.params
            self.decode = eng.decode

        def prefill(self, params, tokens):
            logits, _ = eng.prefill(params, tokens)
            other = (tokens + 1) % eng.model.arch.vocab_size
            return logits, eng.prefill(params, other)[1]

    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.consistency_phase(OtherCache(),
                                     reduced_backend.prompt_len)


def test_kernel_phase_reduced():
    out = chip_smoke.kernel_phase(ARCHS[chip_smoke.ARCH].reduced(),
                                  batch=1, seq=256)
    assert out["flash_err"] < chip_smoke.KERNEL_ATOL
    assert out["decode_err"] < chip_smoke.KERNEL_ATOL
    assert out["mosaic"] is False       # interpret mode on the CPU


def test_engine_backend_no_compile_in_service(reduced_backend):
    """Every batch size 1..max_batch was compiled when the plan was
    bound, so no service_s call compiles."""
    g = chip_smoke.smoke_graph(reduced_backend.prompt_len,
                               reduced_backend.max_new)
    cfg = Planner(g, Profiler(g), s_avail=chip_smoke.S_AVAIL,
                  max_tuples_per_task=32, bb_nodes=4,
                  bb_time_s=1.0).plan(chip_smoke.PLAN_RPS)
    reduced_backend.bind(g, cfg)
    srv = Server(cfg.instances()[0][0], 0)
    rng = np.random.default_rng(0)
    with chip_smoke.compile_watch() as seen:
        for b in range(1, reduced_backend.max_batch + 1):
            assert reduced_backend.service_s(srv, [None] * b, 0.0, rng) > 0
    assert seen.n == 0


def test_compile_watch_counts_a_compile():
    with chip_smoke.compile_watch() as seen:
        jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert seen.n >= 1 and seen.seconds > 0


def test_compile_cache_dir(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def _run_script(cwd: Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_script_fails_off_the_chip(where, tmp_path):
    """On the CPU, and without the rest of the repo, the script exits
    non-zero and prints no result."""
    cwd, why = REPO, "SmokeFailure: no TPU"
    if where == "script_alone":
        shutil.copy(REPO / "chip_smoke.py", tmp_path)
        cwd, why = tmp_path, "ModuleNotFoundError"
    proc = _run_script(cwd)
    assert proc.returncode != 0
    assert why in proc.stderr
    assert '"ok"' not in proc.stdout
