#!/usr/bin/env python3
"""Compile a sim cell's program at its real size for a described TPU v5e,
with no chip: what the chip's compiler refuses shows here, and
``memory_analysis()`` gives the program's bytes.  Nothing runs.

    JAX_PLATFORMS=cpu python bench/rehearse.py paper_cnn.case1b [...]

The program picks its kernels from ``jax.default_backend()``, which is the
CPU here, so this script steers the compute dispatch to the compiled Pallas
kernels for the compile.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.dispatch as dispatch
    from bench import cells, traffic_gen
    from bench.engines import sim

    jax.config.update("jax_enable_compilation_cache", False)
    os.environ[dispatch.ENV_VAR] = "pallas"
    dispatch._interpret = lambda backend: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for name in argv:
        cell = cells.load_cell(name)
        cfg, tr = cell.config, cell.traffic
        engine = cells.engine(cfg, tr)(cfg, tr, None)
        ds = engine.dataset()
        engine.check_model(ds)
        f = sim.grid_fn(cfg, tr, ds, engine.workload())
        plan = traffic_gen.trial_plan(cfg, tr, np.random.default_rng(0))
        r, s = tr["seeds_per_call"], len(tr["strategies"])
        args = (jax.ShapeDtypeStruct((1, r) + plan.shape, jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((s,), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((r,), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((1,) + plan.shape[:2], jnp.float32,
                                     sharding=one))
        try:
            compiled = jax.jit(f).lower(*args).compile()
        except Exception as e:  # the chip's compiler refused it: report
            print(f"{name}: refused: {str(e).splitlines()[0][:400]}")
            continue
        ma = compiled.memory_analysis()
        print(f"{name}: temp {ma.temp_size_in_bytes} B, arguments "
              f"{ma.argument_size_in_bytes} B, output "
              f"{ma.output_size_in_bytes} B, code "
              f"{ma.generated_code_size_in_bytes} B; tpu_custom_call "
              f"{compiled.as_text().count('custom_call_target=\"tpu_custom_call\"')}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
