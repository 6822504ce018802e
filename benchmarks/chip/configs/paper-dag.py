"""The paper's evaluation deployment: layered random mixed-mode DAGs, one
at a time, closed loop, through the program's threaded runtime.

Binding: each DAG of the copied generator (``yardstick/dag_gen.py``)
becomes a program ``TaoDag``; each TAO carries ``chunks_per_tao`` chunks,
and chunk ``i`` of a TAO of type ``t`` is one call of the payload
``tao_<t>`` on input ``i`` of that type, through ``repro.kernels.ops``
(Pallas on a TPU).  The configuration's DAGs, one per degree, are built
once and run in turn, back to back, through ``ThreadedRuntime.run_workload``
on one runtime, so its PTT keeps learning.

Plain reference: ``jnp.matmul`` at ``HIGHEST`` precision, ``jnp.sort`` and
the identity, on the same inputs, for a sample of (TAO, chunk) outputs
drawn from the seed; the DAG's own edges, against which every TAO must
have run exactly once and after all its parents; and a count of the
payload calls, in which every chunk of every run appears exactly once.
"""
from __future__ import annotations

import collections
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import dag_gen, work

KERNELS = ("matmul", "sort", "copy")


def _payloads(control: bool, fault: str | None, mix: dict):
    """The jitted payload of each TAO type, named ``tao_<type>`` so that the
    trace reduction finds it.  ``control`` puts the reference, in the next
    precision below the payload's, in the matmul's place."""
    from repro.kernels import ops

    def tao_matmul(x):
        if control:
            return _lower_precision_matmul(x, mix["control"])
        out = ops.matmul(x, x)
        if fault == "alter_answer":
            out = out.at[0, 0].add(1.0)
        return out

    def tao_sort(x):
        if fault == "unchanged_state":
            return ops.copy(x)
        return ops.sort_rows(x)

    def tao_copy(x):
        return ops.copy(x)

    return {"matmul": jax.jit(tao_matmul), "sort": jax.jit(tao_sort),
            "copy": jax.jit(tao_copy)}


def _lower_precision_matmul(x, how: str):
    """``x @ x`` in the precision below the payload's: ``high`` (three
    bf16 passes) for float32 at ``highest``; operands rounded to fp8
    (e4m3: 4 exponent bits, 3 mantissa bits) for bfloat16.  The rounding
    is ``reduce_precision``: XLA may drop a pair of converts to float8
    and back as a no-op."""
    if how == "high":
        return jnp.matmul(x, x, precision=jax.lax.Precision.HIGH,
                          preferred_element_type=jnp.float32)
    if how == "fp8":
        q = jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
        return jnp.matmul(q, q, preferred_element_type=jnp.float32
                          ).astype(x.dtype)
    raise ValueError(f"unknown control {how!r}")


def reference(kind: str, x):
    """The plain answer of one chunk."""
    if kind == "matmul":
        xf = x.astype(jnp.float32)
        return jnp.matmul(xf, xf, precision=jax.lax.Precision.HIGHEST)
    if kind == "sort":
        return jnp.sort(x, axis=-1)
    return x


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.mix = ctx.config, ctx.traffic
        self.n_chunks = int(self.cfg["chunks_per_tao"])
        self.outputs: dict = {}     # (run, node, chunk) -> device array
        self.calls: list = []       # (run, node, chunk) of every chunk call
        self.runs: list = []        # (opened at, WorkloadResult, shape)
        self.kept: list = []        # per run: node -> the chunk kept
        self.run_index = -1         # the run in flight (-1: warm-up)
        self.keep: dict = {}        # its node -> the chunk kept

    # -- set-up ---------------------------------------------------------------
    def _inputs(self):
        """Every payload input, made on the device in one call."""
        spec = self.mix["payloads"]

        def make(key):
            out = {}
            for ti, t in enumerate(KERNELS):
                shape, dtype = tuple(spec[t]["shape"]), spec[t]["dtype"]
                out[t] = [jax.random.normal(
                    jax.random.fold_in(key, ti * 64 + i), shape,
                    jnp.float32).astype(dtype) for i in range(self.n_chunks)]
            return out

        return jax.jit(make)(jax.random.key(self.ctx.seed % 2**32))

    def _shapes(self):
        """One DAG per degree of the configuration, drawn from the seed."""
        rng = random.Random(self.ctx.seed)
        return [dag_gen.random_dag(int(self.cfg["n_tasks"]), degree,
                                   kernel_types=KERNELS,
                                   seed=rng.randrange(2**62))
                for degree in self.cfg["degrees"]]

    def _sample(self, d: int, shape) -> dict:
        """node -> the chunk whose output run ``d`` keeps for the check:
        ``check_per_dag`` TAOs of each type, at most ``check_max_per_type``
        of a type in the window, drawn from the seed."""
        per = int(self.mix["check_per_dag"])
        cap = int(self.mix["check_max_per_type"])
        rng = random.Random(self.ctx.seed * 1_000_003 + d)
        out = {}
        for t in KERNELS:
            nodes = [i for i, ty in enumerate(shape.types) if ty == t]
            k = min(per, len(nodes), max(0, cap - self._taken.get(t, 0)))
            for node in rng.sample(nodes, k):
                out[node] = rng.randrange(self.n_chunks)
            self._taken[t] = self._taken.get(t, 0) + k
        return out

    def _program_dag(self, shape):
        """The program's ``TaoDag`` of ``shape``, its payloads bound."""
        from repro.core import ChunkedWork, TaoDag
        dag = TaoDag()
        nodes = [dag.add_task(t) for t in shape.types]
        for i, ps in enumerate(shape.parents):
            for p in ps:
                dag.add_edge(nodes[p], nodes[i])
        dag.assign_criticality()
        for i, node in enumerate(nodes):
            node.work = ChunkedWork(self._chunk_fn(shape.types[i], i),
                                    self.n_chunks)
        return dag

    def _chunk_fn(self, t: str, node: int):
        """Chunk ``i`` of TAO ``node``: one payload call, counted, its
        output kept where the run in flight keeps it."""
        fn, xs, outputs, calls = (self.fns[t], self.inputs[t], self.outputs,
                                  self.calls)
        skip = self.ctx.fault == "skipped_chunk"

        def run_chunk(i):
            if skip and i == 0:
                return
            out = jax.block_until_ready(fn(xs[i]))
            d = self.run_index
            calls.append((d, node, i))      # list.append: one step, thread-safe
            if self.keep.get(node) == i:
                outputs[(d, node, i)] = out
        if not self.ctx.trace:
            return run_chunk
        span = f"bench.tao.{t}"

        def traced(i):
            with jax.profiler.TraceAnnotation(span):
                run_chunk(i)
        return traced

    def setup(self) -> None:
        from repro.core import ThreadedRuntime, hikey960, make_policy
        if self.cfg["workers"] != "hikey960":
            raise ValueError(f"unknown worker pool {self.cfg['workers']!r}")
        self.inputs = self._inputs()
        self.fns = _payloads(self.ctx.control, self.ctx.fault, self.mix)
        for t in KERNELS:
            for x in self.inputs[t]:
                jax.block_until_ready(self.fns[t](x))
        self.rt = ThreadedRuntime(hikey960(), make_policy(self.cfg["policy"]),
                                  seed=self.ctx.seed)
        self._taken: dict = {}
        self.shapes = self._shapes()
        self.dags = [self._program_dag(s) for s in self.shapes]
        # one smaller DAG warms the pool, its threads and the PTT
        warm = dag_gen.random_dag(int(self.mix["warmup_tasks"]),
                                  self.cfg["degrees"][-1],
                                  kernel_types=KERNELS, seed=self.ctx.seed)
        self._run_one(self._program_dag(warm))
        self.calls.clear()

    def _run_one(self, dag):
        from repro.core.workload import Workload
        wl = Workload()
        wl.add(dag, at=0.0)
        return self.rt.run_workload(wl, timeout_s=float(
            self.mix["dag_timeout_s"]))

    # -- the window -----------------------------------------------------------
    def measure(self, seconds: float) -> None:
        t_open = time.perf_counter()
        d = 0
        while time.perf_counter() - t_open < seconds:
            k = d % len(self.dags)
            self.keep = self._sample(d, self.shapes[k])
            self.kept.append(self.keep)
            self.run_index = d
            at = time.perf_counter() - t_open
            res = self._run_one(self.dags[k])
            self.runs.append((at, res, self.shapes[k]))
            d += 1

    def result(self, seconds: float) -> dict:
        done = sum(1 for at, res, _ in self.runs for rec in res.trace
                   if at + rec.end <= seconds)
        attempted = sum(len(s.types) for _, _, s in self.runs)
        completed = sum(res.completed for _, res, _ in self.runs)
        return {"attempted": attempted, "failed": attempted - completed,
                "metrics": {"taos_per_s": done / seconds}}

    # -- what per-layer readers read ----------------------------------------
    def launch_gaps(self) -> list:
        """Seconds from the last parent's end to each TAO's start."""
        out = []
        for _, res, shape in self.runs:
            rec = {r.tao_id: r for r in res.trace}
            for i, ps in enumerate(shape.parents):
                if ps and i in rec and all(p in rec for p in ps):
                    out.append(rec[i].start - max(rec[p].end for p in ps))
        return out

    def payload_work(self, t: str) -> work.Work:
        spec = self.mix["payloads"][t]
        shape = tuple(spec["shape"])
        item = jnp.dtype(spec["dtype"]).itemsize
        if t == "matmul":
            return work.matmul(shape[0], shape[1], shape[1], item, item)
        if t == "sort":
            return work.sort_rows(shape[0], shape[1], item)
        return work.copy(shape, item)

    # -- correctness ------------------------------------------------------------
    def release(self) -> None:
        self.dags = []
        self.fns = None

    def checks(self) -> list:
        from yardstick.checks import Check
        not_once = before_parents = 0
        calls = collections.Counter(self.calls)
        chunks_not_once = sum(
            1 for d, (_, _, shape) in enumerate(self.runs)
            for node in range(len(shape.types)) for i in range(self.n_chunks)
            if calls.pop((d, node, i), 0) != 1) + len(calls)
        for _, res, shape in self.runs:
            seen: dict = {}
            for r in res.trace:
                seen.setdefault(r.tao_id, []).append(r)
            not_once += sum(1 for i in range(len(shape.types))
                            if len(seen.get(i, ())) != 1)
            for i, ps in enumerate(shape.parents):
                if len(seen.get(i, ())) != 1:
                    continue
                start = seen[i][0].start
                before_parents += sum(
                    1 for p in ps
                    if len(seen.get(p, ())) == 1 and seen[p][0].end > start)
        worst = {t: 0.0 for t in KERNELS}
        wrong = {t: 0 for t in KERNELS}
        missing = 0
        refs: dict = {}
        for d, (_, _, shape) in enumerate(self.runs):
            for node, chunk in self.kept[d].items():
                t = shape.types[node]
                out = self.outputs.get((d, node, chunk))
                if out is None:
                    missing += 1
                    continue
                if (t, chunk) not in refs:
                    refs[(t, chunk)] = np.asarray(
                        reference(t, self.inputs[t][chunk]), np.float32)
                want = refs[(t, chunk)]
                got = np.asarray(out, np.float32)
                if t == "matmul":
                    err = float(np.max(np.abs(got - want))
                                / np.max(np.abs(want)))
                    worst[t] = max(worst[t], err)
                elif not np.array_equal(got, want):
                    wrong[t] += 1
        limits = self.mix["limits"]
        return [
            Check("taos_not_run_once", float(not_once), 0.0),
            Check("chunk_calls_not_once", float(chunks_not_once), 0.0),
            Check("taos_started_before_a_parent_ended",
                  float(before_parents), 0.0),
            Check("sampled_answers_missing", float(missing), 0.0),
            Check("matmul_max_err_over_max_ref", worst["matmul"],
                  float(limits["matmul_max_err_over_max_ref"])),
            Check("sort_answers_wrong", float(wrong["sort"]), 0.0),
            Check("copy_answers_wrong", float(wrong["copy"]), 0.0),
        ]
