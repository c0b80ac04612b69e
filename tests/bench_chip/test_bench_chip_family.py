"""A model family arrives as files alone.

The chip benchmark finds a configuration's family by name
(``families/<family>.py``), as it finds a traffic mix or a metric's
reader. Here a checkout of the benchmark gains the repo's attention-free
Mamba2 family (``family_ssm.py`` beside this test, copied to
``families/ssm.py``), a configuration that names it and a cell, as new
files and ``BENCHMARK.json`` entries only; every file the benchmark
already has stays byte for byte. The cell is served through the gateway,
``EngineBackend`` and ``Engine`` at a tiny size and comes out ``correct``;
decoding from a zeroed recurrent state does not. Each family's weights,
reference and counts are held to the repo's ``Model`` at reduced sizes.
"""
import functools
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
HOME = REPO / "benchmarks" / "chip"
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(HERE))
from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import weights as W  # noqa: E402
from benchmarks.chip import work  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.sharding.policy import ShardingPolicy  # noqa: E402
from test_bench_chip_harness import SEED, WINDOW_S  # noqa: E402
from test_bench_chip_reference import SMALL  # noqa: E402

CELL = "tiny-ssm.quick"
ARCH = "tiny-ssm"
# the tiny Mamba2's limit: bf16 serving reads widest gaps of 0-0.006 here,
# the fp8 control 0.043-0.123 (CPU readings of control.py on 8 seeds)
SSM_LIMIT = 0.015

# Mamba2Config keys of a reduced mamba2-130m: two chunks in an 8-token
# prompt
SSM_SMALL = dict(hidden_size=64, num_hidden_layers=2, vocab_size=512,
                 num_heads=8, head_dim=16, state_size=16, conv_kernel=4,
                 chunk_size=4, n_groups=1, layer_norm_epsilon=1e-5,
                 tie_word_embeddings=True, use_conv_bias=False,
                 use_bias=False, repo_arch="mamba2-130m")

# (family, its module's home, the configuration's keys)
CASES = {
    "dense-granite": ("dense", None, {**SMALL["granite-3-2b"],
                                      "head_dim": 16,
                                      "repo_arch": "granite-3-2b"}),
    "dense-qwen2": ("dense", None, {**SMALL["qwen2-7b"], "head_dim": 16,
                                    "repo_arch": "qwen2-7b"}),
    "ssm-mamba2": ("ssm", "checkout", SSM_SMALL),
}


def family_checkout(root: Path) -> Path:
    """A checkout of the benchmark with the ssm family, a configuration
    that names it, a traffic mix and a cell, added as files and entries
    only."""
    home = root / "benchmarks" / "chip"
    shutil.copytree(HOME, home, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(HERE / "family_ssm.py", home / "families" / "ssm.py")
    base = json.loads((HOME / "configs" / "ta-granite.json").read_text())
    cfg = {k: base[k] for k in ("app", "tasks", "fanout", "slo_ms",
                                "staleness_ms", "s_avail", "sample_rows")}
    cfg.update(SSM_SMALL, family="ssm", served_as=ARCH, knee_rps=20.0,
               engine={"full_width": True, "max_batch": 4, "max_seq": 32},
               limits={f"max_gap.{ARCH}": SSM_LIMIT})
    (home / "configs" / "tiny-ssm.json").write_text(json.dumps(cfg))
    tr = json.loads((HOME / "traffic" / "steady.json").read_text())
    tr.update(prompt_len=8, max_new=4, rate_of_knee=1.0, drain_s=5.0)
    (home / "traffic" / "quick-ssm.json").write_text(json.dumps(tr))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-ssm", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-ssm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-ssm",
                               "traffic": "quick-ssm", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return family_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="module")
def tiny(checkout):
    """The ssm cell, and a ``deploy`` that reuses one backend's engines
    (each run loads its seed's weights into them)."""
    from repro.core import profiler
    cell = H.load_cell(checkout, CELL)
    mp = pytest.MonkeyPatch()
    mp.setattr(profiler, "Profiler",
               functools.partial(profiler.Profiler, batches=(1, 2, 4)))
    real_deploy, shared = H.deploy, {}

    def deploy(cell, dims, seed, backend=None):
        dep = real_deploy(cell, dims, seed, shared.get("backend"))
        shared["backend"] = dep.backend
        return dep

    mp.setattr(H, "deploy", deploy)
    try:
        yield cell, shared
    finally:
        mp.undo()


def _family(case, checkout):
    family, where, keys = CASES[case]
    home = checkout / "benchmarks" / "chip" if where else HOME
    fam = H.load_family(home, family)
    m = fam.Dims.from_keys(f"{case}-small", keys)
    return fam, m, fam.repo_arch(m, keys)


# ---------------------------------------------------------------------------
# the contract, family by family
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_familys_tree_is_the_models(case, checkout):
    fam, m, arch = _family(case, checkout)
    model = Model(arch, ShardingPolicy(mesh=None), param_dtype=jnp.bfloat16)
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.eval_shape(fam.served_params, m, W.seed_words(SEED))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert W.family_of(m) is fam


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_familys_layer_equals_the_stacked_draw(case, checkout):
    fam, m, _ = _family(case, checkout)
    words = W.seed_words(SEED)
    full = fam.served_params(m, words)
    for layer in range(m.layers):
        one = fam.layer_weights(m, words, np.uint32(layer))
        assert set(one) == set(full["blocks"])
        for name, leaf in one.items():
            np.testing.assert_array_equal(
                np.asarray(leaf, np.float32),
                np.asarray(full["blocks"][name][layer], np.float32))


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_familys_reference_is_the_models_forward(case, checkout):
    """The float32 model and the family's reference on the same
    (bf16-drawn) weights; for Mamba2 the model runs the chunked dual form
    and the reference the recurrence, token by token."""
    fam, m, arch = _family(case, checkout)
    words = W.seed_words(SEED)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          fam.served_params(m, words))
    model = Model(arch, ShardingPolicy(mesh=None), param_dtype=jnp.float32)
    tokens = np.random.default_rng(3).integers(0, m.vocab, (3, 12),
                                               dtype=np.int32)
    want = np.asarray(jax.jit(model.forward)(params, jnp.asarray(tokens)))
    got = np.asarray(fam.logits(m, words, tokens, 0))
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    assert np.abs(np.asarray(fam.logits(m, words, tokens, 0, quant="fp8"))
                  - got).max() > 1e-4 * np.abs(got).max()


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_counts_are_the_familys(case, checkout):
    """``work`` gives each family's own counts, through the dims."""
    fam, m, _ = _family(case, checkout)
    assert work.prefill_flops(m, 3, 8) == fam.prefill_flops(m, 3, 8) > 0
    assert work.decode_flops(m, 3, 9) == fam.decode_flops(m, 3, 9) > 0
    assert work.decode_bytes(m, 3, 9) == fam.decode_bytes(m, 3, 9) > 0


def test_ssm_counts_match_shapes_worked_by_hand(checkout):
    fam = H.load_family(checkout / "benchmarks" / "chip", "ssm")
    m = fam.Dims("hand", vocab=10, d_model=4, layers=1, heads=2,
                 head_dim=4, d_state=2, conv_width=2, chunk_size=4,
                 tied=True, norm_eps=1e-5)
    # per token: in-projections 4 * (2*8 + 2*2 + 2) = 88 and wo 8*4 = 32
    # MACs, conv taps 2 * (8 + 2*2) = 24, state 2 * 8*2 = 32: 176 MACs
    assert fam.prefill_flops(m, 1, 3) == 2 * (3 * 176 + 4 * 10)
    assert fam.decode_flops(m, 2, 7) == 2 * 2 * (176 + 4 * 10)
    # bf16 leaves: norm 4, wz wx 32 each, wB wC wdt 8 each, convs 16 + 4
    # + 4, D 2, gate_norm 8, wo 32 = 158 values; A_log, dt_bias 2 f32 each
    assert m.layer_bytes() == 2 * 158 + 4 * 4
    # + final norm and tied table; per row the f32 state (16) read and
    # written, and the conv tail of 8 + 4 channels over 2 positions
    assert fam.decode_bytes(m, 2, 7) == (332 + 2 * (4 + 40)
                                        + 2 * (2 * 4 * 16 + 2 * 2 * 12))


# ---------------------------------------------------------------------------
# a cell of the new family
# ---------------------------------------------------------------------------
def test_the_benchmarks_files_are_unchanged_in_the_checkout(checkout):
    """Only new files and new ``BENCHMARK.json`` entries differ."""
    home = checkout / "benchmarks" / "chip"
    mine = {p.relative_to(HOME) for p in HOME.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}
    for rel in mine:
        assert (home / rel).read_bytes() == (HOME / rel).read_bytes(), rel
    theirs = {p.relative_to(home) for p in home.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    assert theirs - mine == {Path("families/ssm.py"),
                             Path("configs/tiny-ssm.json"),
                             Path("traffic/quick-ssm.json")}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    got = json.loads((checkout / "BENCHMARK.json").read_text())
    for key, entries in bench.items():
        if isinstance(entries, list) and key in ("configs", "workloads"):
            assert got[key][:len(entries)] == entries
            assert len(got[key]) == len(entries) + 1
        else:
            assert got[key] == entries, key


def test_a_cell_of_a_family_brought_as_files_is_correct(tiny):
    cell, shared = tiny
    assert cell.family.__name__.endswith(".ssm")
    out = H.run(cell, SEED, WINDOW_S, False, time.monotonic(),
                lambda msg: None)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 60 and out["failed"] == 0
    gap = out["checks"][f"max_gap.{ARCH}"]
    assert 0 <= gap["value"] <= gap["limit"] == SSM_LIMIT
    assert shared["backend"].served[ARCH].model.arch.family == "ssm"


def _zeroed_state(decode):
    """Each decode step starts from a recurrent state of zeros."""
    def zeroed(params, cache, cache_len, tokens):
        return decode(params, jax.tree.map(jnp.zeros_like, cache),
                      cache_len, tokens)
    return zeroed


def test_decoding_from_a_zeroed_state_is_not_correct(tiny):
    cell, shared = tiny
    H.run(cell, SEED, WINDOW_S, False, time.monotonic(), lambda msg: None)
    eng = shared["backend"].served[ARCH]
    decode = eng.decode
    eng.decode = _zeroed_state(decode)
    try:
        out = H.run(cell, SEED + 1, WINDOW_S, False, time.monotonic(),
                    lambda msg: None)
    finally:
        eng.decode = decode
    assert out["correct"] is False
    gap = out["checks"][f"max_gap.{ARCH}"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("family", [None, "nowhere"],
                         ids=["no_family_key", "no_family_module"])
def test_load_cell_needs_the_configs_family(checkout, tmp_path, family):
    root = tmp_path / "root"
    shutil.copytree(checkout, root)
    path = root / "benchmarks" / "chip" / "configs" / "tiny-ssm.json"
    cfg = json.loads(path.read_text())
    cfg.pop("family")
    if family is not None:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    with pytest.raises(H.BenchError, match="family"):
        H.load_cell(root, CELL)
