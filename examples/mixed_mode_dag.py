"""Mixed-mode DAG on the threaded runtime with real kernels.

Each TAO executes actual JAX work matching its paper class:
  matmul -> blocked matrix multiply     (compute-bound)
  sort   -> row sort                    (data-reuse)
  copy   -> streaming array copy        (memory-bound)

TAOs are moldable: a TAO's chunks are claimed by every worker of its elastic
place, so a width-4 TAO really runs on 4 threads (jitted JAX releases the
GIL).  The PTT records per-(leader, width) times and molding adapts widths.

Run:  PYTHONPATH=src python examples/mixed_mode_dag.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ChunkedWork, ThreadedRuntime, hikey960, make_policy,
                        random_dag)
from repro.kernels import ops

RNG = np.random.default_rng(0)
MAT = jnp.asarray(RNG.standard_normal((256, 256)), jnp.float32)
ROWS = jnp.asarray(RNG.standard_normal((32, 1024)), jnp.float32)
STREAM = jnp.asarray(RNG.standard_normal((2048, 256)), jnp.float32)

# auto dispatch: the Pallas kernels on a TPU, their XLA references elsewhere
matmul_j = jax.jit(lambda x: ops.matmul(x, x))
sort_j = jax.jit(ops.sort_rows)
copy_j = jax.jit(ops.copy)


def bind_real_work(dag) -> None:
    work = {
        "matmul": lambda i: matmul_j(MAT).block_until_ready(),
        "sort": lambda i: sort_j(ROWS).block_until_ready(),
        "copy": lambda i: copy_j(STREAM).block_until_ready(),
    }
    for node in dag.nodes:
        node.work = ChunkedWork(work[node.type], n_chunks=4)


def main() -> None:
    # warm the jit caches so worker threads measure steady-state kernels
    matmul_j(MAT).block_until_ready()
    sort_j(ROWS).block_until_ready()
    copy_j(STREAM).block_until_ready()

    for policy in ("homogeneous", "molding:weight"):
        dag = random_dag(n_tasks=300, target_degree=3.0, seed=1)
        bind_real_work(dag)
        rt = ThreadedRuntime(hikey960(), make_policy(policy), seed=0)
        out = rt.run(dag, timeout_s=300)
        print(f"{policy:16s} {out['throughput_taos_per_s']:8.1f} TAOs/s "
              f"({out['completed']} TAOs, {out['elapsed_s']:.2f}s)")
        # peek at what the PTT learned
        for t in rt.core.ptt.types():
            table = rt.core.ptt.table(t)
            times = [f"w{w}={table.time(0, w) * 1e3:.2f}ms"
                     for w in (1, 2, 4) if table.time(0, w) > 0]
            if times:
                print(f"    PTT[{t}] leader0: {', '.join(times)}")


if __name__ == "__main__":
    main()
