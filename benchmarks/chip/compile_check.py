"""Compile a cell's programs for a described TPU v5e chip, with no chip
attached, and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py <cell> \\
        [--batch 4] [--prompt-len 128] [--max-seq 256]

For every model of the cell's configuration: the program's own weight
init (``jax.jit(Model.init)``, which ``EngineBackend`` runs first), the
benchmark's seeded draw (its family's ``served_params``), and the
engine's prefill and decode programs at the cell's shapes. What the
compiler refuses here costs no chip time. Nothing runs, so this gives no
times.
"""
import argparse
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-seq", type=int, default=256)
    args = ap.parse_args(argv)
    if sys.path and Path(sys.path[0]).resolve() == HERE:
        sys.path.pop(0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.chip import harness as H
    from benchmarks.chip import weights as W
    from repro.configs import ARCHS
    from repro.models import Model
    from repro.serving.engine import Engine, EngineConfig
    from repro.sharding.policy import ShardingPolicy

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
    cell = H.load_cell(ROOT, args.workload)
    B, S = args.batch, args.prompt_len
    total = 0
    with H.registered(cell) as dims:
        for name, m in dims.items():
            model = Model(ARCHS[name], ShardingPolicy(mesh=None),
                          param_dtype=jnp.bfloat16)
            eng = Engine(model, None, EngineConfig(max_batch=B,
                                                   max_seq=args.max_seq))
            key = spec(jax.eval_shape(lambda: jax.random.key(0)))
            words = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
            params = jax.tree.map(spec, jax.eval_shape(model.init,
                                                       jax.random.key(0)))
            tokens = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=chip)
            cache = jax.tree.map(spec, jax.eval_shape(
                lambda p, t: eng.prefill(p, t)[1], params, tokens))
            progs = {
                "program init": jax.jit(model.init).lower(key),
                "seeded draw": W.family_of(m).served_params.lower(m, words),
                "prefill": eng.prefill.lower(params, tokens),
                "decode": eng.decode.lower(
                    params, cache, jax.ShapeDtypeStruct((), jnp.int32,
                                                        sharding=chip),
                    jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=chip)),
            }
            weights = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(params))
            total += weights
            print(f"{name}: weights {weights / 1e9:.3f} GB", flush=True)
            for prog, lowered in progs.items():
                ma = lowered.compile().memory_analysis()
                print(f"  {prog}: arguments "
                      f"{ma.argument_size_in_bytes / 1e9:.3f} GB, outputs "
                      f"{ma.output_size_in_bytes / 1e9:.3f} GB, temporaries "
                      f"{ma.temp_size_in_bytes / 1e9:.3f} GB", flush=True)
    print(f"all weights: {total / 1e9:.3f} GB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
