"""Compile the main path for a described TPU v5e chip, with no chip.

The TPU compiler ships with libtpu and compiles for a chip that is
described, not attached.  It refuses what interpret mode accepts: blocks
off the 8x128 tiling, operand types Mosaic has no MXU path for, programs
larger than the chip's memory.  Every Pallas kernel compiles here at real
widths, and so do the full-width granite-3-2b prefill and decode programs
that ``chip_smoke.py`` serves.

The topology is described inside a module fixture, never at import: one
process may hold libtpu at a time, and pytest-xdist workers all import
this file.  Keep every such compile in this one file.
"""
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402
from repro.configs import ARCHS  # noqa: E402
from repro.kernels.decode_attention import decode_attention_pallas  # noqa: E402
from repro.kernels.flash_attention import flash_attention_pallas  # noqa: E402
from repro.kernels.quant_matmul import quant_matmul_pallas  # noqa: E402
from repro.kernels.ssd_scan import ssd_scan_pallas  # noqa: E402

V5E_HBM_BYTES = 16 * 10**9      # "16 GB" per the Google Cloud v5e page


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: an entry written for a described chip cannot be read back here."""
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _granite_attn_shapes(sharding, batch=8, seq=1024):
    a = ARCHS["granite-3-2b"]
    H, KV, hd = a.num_heads, a.num_kv_heads, a.head_dim
    return (_spec((batch, seq, H, hd), jnp.bfloat16, sharding),
            _spec((batch, seq, KV, hd), jnp.bfloat16, sharding),
            _spec((batch, 1, H, hd), jnp.bfloat16, sharding))


def _kernel_case(name, sharding):
    """(fn, arg specs) of one kernel at the widths the repo serves."""
    if name == "flash_attention":
        q, kv, _ = _granite_attn_shapes(sharding)
        return (lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
                (q, kv, kv))
    if name == "decode_attention":
        _, kv, qd = _granite_attn_shapes(sharding)
        return (decode_attention_pallas,
                (qd, kv, kv, _spec((), jnp.int32, sharding)))
    if name == "quant_matmul":
        a = ARCHS["granite-3-2b"]
        M, K, N = 256, a.d_model, a.d_ff
        return (quant_matmul_pallas,
                (_spec((M, K), jnp.int8, sharding),
                 _spec((K, N), jnp.int8, sharding),
                 _spec((M,), jnp.float32, sharding),
                 _spec((N,), jnp.float32, sharding)))
    assert name == "ssd_scan"
    a = ARCHS["mamba2-130m"]
    s = a.ssm
    B, S = 2, 1024
    nh, hd, ds = s.num_heads(a.d_model), s.head_dim, s.d_state
    f32 = jnp.float32
    return (lambda x, dt, A, Bm, Cm: ssd_scan_pallas(x, dt, A, Bm, Cm,
                                                     chunk=s.chunk_size),
            (_spec((B, S, nh, hd), f32, sharding),
             _spec((B, S, nh), f32, sharding), _spec((nh,), f32, sharding),
             _spec((B, S, ds), f32, sharding),
             _spec((B, S, ds), f32, sharding)))


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "quant_matmul", "ssd_scan"])
def test_kernel_compiles_to_mosaic(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _engine_programs(sharding):
    """The full-width bf16 granite-3-2b engine that chip_smoke serves, and
    abstract arguments for its prefill and decode programs."""
    from repro.models import Model
    from repro.serving.engine import Engine, EngineConfig
    from repro.sharding.policy import ShardingPolicy
    model = Model(ARCHS["granite-3-2b"], ShardingPolicy(mesh=None),
                  param_dtype=jnp.bfloat16)
    eng = Engine(model, None, EngineConfig(max_batch=chip_smoke.MAX_BATCH,
                                           max_seq=chip_smoke.MAX_SEQ))
    place = lambda t: jax.tree.map(          # noqa: E731
        lambda s: _spec(s.shape, s.dtype, sharding), t)
    params = place(model.param_shapes())
    prompt = _spec((chip_smoke.MAX_BATCH, chip_smoke.PROMPT_LEN),
                   jnp.int32, sharding)
    _, cache = jax.eval_shape(eng.prefill, params, prompt)
    tok = _spec((chip_smoke.MAX_BATCH, 1), jnp.int32, sharding)
    return eng, params, prompt, place(cache), tok


@pytest.mark.parametrize("program", ["prefill", "decode", "picked_prefill",
                                     "picked_decode"])
def test_full_width_granite_fits_one_chip(one_chip, program):
    """The engine's programs, and the ones ``generate`` serves with the
    greedy pick folded in (``Engine.picking``)."""
    eng, params, prompt, cache, tok = _engine_programs(one_chip)
    name = program.rsplit("_", 1)[-1]
    fn = (eng.picking(name) if program.startswith("picked_")
          else getattr(eng, name))
    if name == "prefill":
        lowered = fn.lower(params, prompt)
    else:
        lowered = fn.lower(params, cache, _spec((), jnp.int32, one_chip),
                           tok)
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # the weights alone are 5 GB: a program this size is the real model
    assert mem.argument_size_in_bytes > 5 * 10**9
    assert total < V5E_HBM_BYTES, mem
