"""Serve a small model with batched requests through the paper's scheduler.

The weight-based policy + PTT *learn online* that prefill TAOs (compute
bound) belong on big device groups and decode TAOs (HBM-BW bound) on
efficient ones — the paper's mechanism discovering disaggregated
prefill/decode serving.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import random

import jax
import jax.numpy as jnp

from repro.core import hikey960, make_policy
from repro.core.serve_orchestrator import (ServeRequest, simulate_serving,
                                           run_serving_threaded)
from repro.models import ModelConfig, get_model


def main() -> None:
    # ---- 1) fleet-scale scheduling study (simulator) ----------------------
    rng = random.Random(0)
    reqs = [ServeRequest(i, rng.choice([512, 2048, 8192]),
                         rng.choice([64, 128, 256])) for i in range(100)]
    print("=== scheduling study (4 big + 4 LITTLE groups, 100 requests) ===")
    for policy in ("homogeneous", "weight", "molding:weight"):
        st = simulate_serving(reqs, hikey960(), make_policy(policy), seed=0)
        print(f"  {policy:16s} {st.tokens_per_s:8.0f} tok/s   "
              f"mean latency {st.mean_latency:.3f}s   "
              f"p99 {st.p99_latency:.3f}s")

    # ---- 2) real model through the threaded runtime -----------------------
    cfg = ModelConfig(name="serve-demo", family="decoder", n_layers=4,
                      d_model=256, n_heads=4, n_kv_heads=2, d_ff=1024,
                      vocab_size=32000)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0,
                              cfg.vocab_size)
    prefill_j = jax.jit(model.prefill)
    decode_j = jax.jit(model.decode_step)
    _, cache = prefill_j(params, {"tokens": toks})     # warm compile
    decode_j(params, toks[:, -1:], cache)

    small = [ServeRequest(i, 512, 64, arrival=0.02 * i) for i in range(8)]
    out = run_serving_threaded(
        small, hikey960(), make_policy("molding:weight"),
        prefill_fn=lambda r: jax.block_until_ready(
            prefill_j(params, {"tokens": toks})[0]),
        decode_fn=lambda r, i: jax.block_until_ready(
            decode_j(params, toks[:, -1:], cache)[0]))
    print(f"\n=== real model on the threaded runtime ===\n"
          f"  {out.result.completed} TAOs in {out.makespan:.2f}s "
          f"({out.tokens_per_s:.0f} tok/s, p99 sojourn "
          f"{out.p99_latency * 1e3:.1f} ms)")
    for typ, cells in sorted(out.ptt_profiles.items()):
        if not cells:
            continue
        # keys are (leader, width): fold them into a per-width view of
        # what the scheduler learned
        by_width: dict = {}
        for (_, width), t in cells.items():
            by_width.setdefault(width, []).append(t)
        print(f"  measured PTT[{typ}]: {len(cells)} cells")
        for width, ts in sorted(by_width.items()):
            print(f"    w={width}: {len(ts):2d} cells, "
                  f"fastest {min(ts) * 1e3:.2f} ms")


if __name__ == "__main__":
    main()
