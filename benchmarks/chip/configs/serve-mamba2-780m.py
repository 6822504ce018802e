"""One-tenant serving of mamba2-780m at its published widths, through the
program's scheduler: ``build_serving_workload`` ->
``run_serving_workload_threaded`` (Workload -> SchedulerCore ->
ThreadedRuntime) on ``hikey960()`` under the configuration's policy.

Binding: each request is a prefill TAO and a chain of decode TAOs of at
most ``DECODE_UNIT`` tokens.  The prefill TAO runs the request's own
prompt through the model's ``prefill`` and keeps the request's state and
its greedy first token; each decode TAO runs exactly the tokens it
accounts for through ``decode_step``, greedily, from that state.  The
program's tenant zoo serves smoke-sized models with one shared cache, so
the binder lives here and calls only the model's public ``prefill`` and
``decode_step``.

Plain reference: :func:`reference_logits`, a float32 Mamba-2 forward pass
written from the published description (arXiv:2405.21060; ``mamba_ssm``'s
``Mamba2`` layer): sequential SSM recurrence, ``highest`` matmul
precision, no kernels, no cache, layer by layer so that it fits beside
nothing else.  It departs from the description in one way: the logits
cover the padded vocabulary, as the program's do.  It imports nothing of
the program and regenerates the weights from the seed itself.
"""
from __future__ import annotations

import dataclasses
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import requests as traffic_gen
from yardstick import work

HIGHEST = jax.lax.Precision.HIGHEST


# -- weights -------------------------------------------------------------------
def make_weights(cfg: dict, seed: int) -> dict:
    """Every parameter, in the configuration's ``param_dtype``, on the
    device in one jitted call, named as the program's ``Mamba2LM`` names
    them.  The output head is the embedding, transposed (published
    ``tie_embeddings``).  ``cfg["init"]["d_skip"]`` is the skip weight
    ``D``: at ``D = 1`` the skip term is about ten times the recurrence's
    output in norm, so the configuration sets ``D = 0`` and the recurrence
    carries every layer's output."""
    d = work.mamba2_dims(cfg)
    init = cfg["init"]
    L, M, V = d["layers"], d["d_model"], d["vocab"]
    DI, H, K, CD = d["d_inner"], d["heads"], d["d_conv"], d["conv_dim"]

    def make(key):
        ks = iter(jax.random.split(key, 16))
        f32 = jnp.float32

        def normal(shape, std):
            return jax.random.normal(next(ks), shape, f32) * std

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(ks), shape, f32, lo, hi)

        embed = normal((V, M), 0.02)
        dt = jnp.exp(uniform((L, H), np.log(1e-3), np.log(1e-1)))
        params = {
            "embed.w": embed,
            "head.w": embed.T,
            "final_norm.w": jnp.ones((M,), f32),
            "layers.norm.w": jnp.ones((L, M), f32),
            "layers.in_proj.w": normal((L, M, d["d_in_proj"]), 0.02),
            "layers.conv.w": uniform((L, K, CD), -0.5, 0.5),
            "layers.conv.b": uniform((L, CD), -0.5, 0.5),
            "layers.a_log": jnp.log(uniform((L, H), 1.0, 16.0)),
            "layers.d_skip": jnp.full((L, H), init["d_skip"], f32),
            "layers.dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "layers.gate_norm.w": jnp.ones((L, DI), f32),
            "layers.out_proj.w": normal((L, DI, M), 0.02),
        }
        return {k: v.astype(cfg["param_dtype"]) for k, v in params.items()}

    return jax.jit(make)(jax.random.key(seed % 2**32, impl="rbg"))


def make_prompts(reqs, seed: int, vocab: int) -> dict:
    """Request id -> its prompt ids, ``(1, prompt_len)`` int32 on the
    device, one call per prompt length.  A request's ids depend only on the
    seed and its own id."""
    key = jax.random.key((seed + 1) % 2**32)
    out = {}
    for L in sorted({r.prompt_len for r in reqs}):
        ids = [r.id for r in reqs if r.prompt_len == L]
        block = _prompt_block(key, jnp.asarray(ids, jnp.int32) + 2**20,
                              L, vocab)
        for i, rid in enumerate(ids):
            out[rid] = block[i]
    return out


@functools.partial(jax.jit, static_argnames=("length", "vocab"))
def _prompt_block(key, ids, length, vocab):
    return jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(key, i), (1, length), 0, vocab, jnp.int32))(ids)


# -- the plain reference ----------------------------------------------------
def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _fp8(x):
    """Round to fp8 e4m3 (4 exponent, 3 mantissa bits): the control's
    matmul operands.  ``reduce_precision``, because XLA may drop a pair of
    converts to float8 and back as a no-op."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _dot(x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "fp8"))
def _ref_layer(w, i, x, *, dims, eps, fp8):
    """Layer ``i`` over a whole sequence ``x`` ``(S, M)``, float32."""
    d = dict(dims)
    DI, N, H, P, K = (d["d_inner"], d["d_state"], d["heads"],
                      d["head_dim"], d["d_conv"])
    lp = {k[len("layers."):]: v[i] for k, v in w.items()
          if k.startswith("layers.")}
    S = x.shape[0]
    proj = _dot(_rmsnorm(x, lp["norm.w"], eps), lp["in_proj.w"], fp8)
    z, xbc, dt = proj[:, :DI], proj[:, DI:2 * DI + 2 * N], proj[:, -H:]
    # causal depthwise convolution over time, then SiLU
    pad = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    conv = lp["conv.b"] + sum(pad[k:k + S] * lp["conv.w"][k]
                              for k in range(K))
    conv = jax.nn.silu(conv)
    xs = conv[:, :DI].reshape(S, H, P)
    b, c = conv[:, DI:DI + N], conv[:, DI + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                  # (S, H)
    a = -jnp.exp(lp["a_log"])                                 # (H,)

    def step(h, inp):                                         # h: (H, N, P)
        x_t, dt_t, b_t, c_t = inp
        h = (h * jnp.exp(dt_t * a)[:, None, None]
             + dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None, :])
        return h, jnp.einsum("n,hnp->hp", c_t, h, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, N, P), jnp.float32),
                        (xs, dt, b, c))
    y = y + lp["d_skip"][None, :, None] * xs
    y = y.reshape(S, DI) * jax.nn.silu(z)
    y = _rmsnorm(y, lp["gate_norm.w"], eps)
    return x + _dot(y, lp["out_proj.w"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _ref_logits(w, x, rows, *, eps, fp8):
    h = _rmsnorm(x[rows], w["final_norm.w"], eps)
    return _dot(h, w["embed.w"].T, fp8)


def reference_logits(w: dict, cfg: dict, tokens: np.ndarray, rows,
                     fp8: bool = False) -> np.ndarray:
    """Float32 logits at positions ``rows`` of the sequence ``tokens``.
    ``fp8`` rounds every matmul's operands to fp8 e4m3: the control."""
    d = work.mamba2_dims(cfg)
    dims = tuple(sorted(d.items()))
    eps = float(cfg["norm_epsilon"])
    x = jnp.take(w["embed.w"], jnp.asarray(tokens, jnp.int32), axis=0)
    for i in range(d["layers"]):
        x = _ref_layer(w, i, x, dims=dims, eps=eps, fp8=fp8)
    return np.asarray(_ref_logits(w, x, jnp.asarray(rows, jnp.int32),
                                  eps=eps, fp8=fp8))


def served_gaps(ref: np.ndarray, tokens) -> np.ndarray:
    """Per position, how far the served token's logit lies below the
    reference's best."""
    ref = np.asarray(ref, np.float64)
    return ref.max(-1) - ref[np.arange(len(tokens)), np.asarray(tokens)]


# -- the cell -------------------------------------------------------------------
def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the published sizes."""
    from repro.models import ModelConfig
    ssm = cfg["ssm_cfg"]
    if ssm["ngroups"] != 1 or cfg["d_intermediate"] or cfg["attn_layer_idx"]:
        raise ValueError("the program's Mamba2LM has one B/C group, no MLP "
                         "and no attention layers")
    return ModelConfig(
        name="mamba2-780m", family="ssm", n_layers=cfg["n_layer"],
        d_model=cfg["d_model"], n_heads=1, n_kv_heads=1, d_ff=0,
        vocab_size=cfg["vocab_size"], ssm_state=ssm["d_state"],
        ssm_expand=ssm["expand"], ssm_head_dim=ssm["headdim"],
        ssm_conv=ssm["d_conv"], ssm_chunk=ssm["chunk_size"],
        norm_eps=cfg["norm_epsilon"],
        vocab_pad_multiple=cfg["pad_vocab_size_multiple"])


@dataclasses.dataclass
class _Served:
    tokens: list            # device (1, 1) int32 ids, in order served
    tok: object = None      # the last token
    cache: object = None    # the request's state while it is open


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.mix = ctx.config, ctx.traffic
        self.served: dict = {}       # request id -> _Served
        self.prefill_lengths: list = []

    # -- programs ---------------------------------------------------------------
    def _programs(self):
        from repro.models import get_model
        model = get_model(program_config(self.cfg))
        fault = self.ctx.fault

        def greedy(logits):
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            return tok.astype(jnp.int32)

        def mamba2_prefill(params, tokens):
            logits, cache = model.prefill(params, {"tokens": tokens})
            return greedy(logits), cache

        def mamba2_decode(params, tok, cache):
            logits, new = model.decode_step(params, tok, cache)
            out = greedy(logits)
            if fault == "alter_token":
                out = (out + 1) % logits.shape[-1]
            if fault == "unchanged_state":
                new = cache
            if fault == "unchanged_ssd_state":      # the conv window moves on
                new = dict(new, ssd=cache["ssd"])
            return out, new

        return jax.jit(mamba2_prefill), jax.jit(mamba2_decode)

    def _binder(self):
        """``binder(tao, r)`` for the program's serving path: called for a
        request's nodes in order, prefill first."""
        from repro.core import ChunkedWork
        from repro.core.serve_orchestrator import DECODE_UNIT
        params, prefill, decode = self.params, self.prefill, self.decode
        prompts, served = self.prompts, self.served
        tracing, fault = self.ctx.trace, self.ctx.fault
        left: dict = {}

        def run_prefill(r):
            tok, cache = prefill(params, prompts[r.id])
            jax.block_until_ready(tok)
            served[r.id] = _Served([tok], tok, cache)
            self.prefill_lengths.append(r.prompt_len)

        def run_decode(r, n, last):
            s = served[r.id]
            tok, cache = s.tok, s.cache
            if fault == "dropped_ssd_state":    # lost between prefill and decode
                cache = dict(cache, ssd=jnp.zeros_like(cache["ssd"]))
            for _ in range(n):
                tok, cache = decode(params, tok, cache)
                s.tokens.append(tok)
            jax.block_until_ready(tok)
            s.tok, s.cache = tok, (None if last else cache)

        def wrap(fn, span):
            if not tracing:
                return fn

            def traced(i):
                with jax.profiler.TraceAnnotation(span):
                    fn(i)
            return traced

        def binder(tao, r):
            if tao.type == "prefill":
                left[r.id] = r.gen_len
                tao.work = ChunkedWork(
                    wrap(lambda i: run_prefill(r), "bench.prefill"), 1)
                return
            n = min(DECODE_UNIT, left[r.id])
            left[r.id] -= n
            last = left[r.id] == 0
            tao.work = ChunkedWork(
                wrap(lambda i: run_decode(r, n, last), "bench.decode"), 1)

        return binder

    def _program_requests(self, reqs):
        from repro.core.serve_orchestrator import ServeRequest
        return [ServeRequest(id=r.id, prompt_len=r.prompt_len,
                             gen_len=r.output_len, arrival=r.arrival)
                for r in reqs]

    # -- set-up -------------------------------------------------------------------
    def setup(self) -> None:
        from repro.core import ThreadedRuntime, hikey960, make_policy
        if self.cfg["workers"] != "hikey960":
            raise ValueError(f"unknown worker pool {self.cfg['workers']!r}")
        seconds = (float(self.mix["trace_seconds"]) if self.ctx.trace
                   else self.ctx.seconds)
        self.reqs = traffic_gen.generate(self.mix, seconds)
        self.by_id = {r.id: r for r in self.reqs}
        self.params = make_weights(self.cfg, self.ctx.seed)
        self.prefill, self.decode = self._programs()
        self.policy = make_policy(self.cfg["policy"])
        self.rt = ThreadedRuntime(hikey960(), self.policy, seed=self.ctx.seed)
        # warm-up: one request of each prompt length through the same path,
        # which compiles (or loads) every program the window runs
        lengths = sorted(set(self.mix["prompt_lengths"]))
        warm = [traffic_gen.Request(-1 - i, L, 2, 0.0)
                for i, L in enumerate(lengths)]
        self.prompts = make_prompts(warm + self.reqs, self.ctx.seed,
                                    int(self.cfg["vocab_size"]))
        self._serve(warm)
        self.served.clear()
        self.prefill_lengths.clear()

    def _serve(self, reqs):
        from repro.core.serve_orchestrator import run_serving_workload_threaded
        return run_serving_workload_threaded(
            self._program_requests(reqs), self.rt.spec, self.policy,
            self._binder(), seed=self.ctx.seed,
            timeout_s=float(self.mix["drain_timeout_s"]), runtime=self.rt)

    # -- the window -----------------------------------------------------------
    def measure(self, seconds: float) -> None:
        self.stats = self._serve(self.reqs)

    def _request_of(self, st):
        return self.by_id[int(st.name[len("req"):])]

    def result(self, seconds: float) -> dict:
        """TTFT: end of the prefill TAO - when the request was due;
        sojourn: end of its last TAO - when it was due; a request that did
        not finish counts as infinitely late.  Tokens: the prefill's first
        and each decode TAO's ``n``, where the TAO ended in the window."""
        from repro.core.serve_orchestrator import DECODE_UNIT
        from yardstick.stats import percentile
        res = self.stats.result
        per_dag = res.per_dag
        prefill_end, tokens = {}, 0
        for rec in res.trace:
            if rec.type == "prefill":
                prefill_end[rec.dag_id] = rec.end
            if rec.end > seconds:
                continue
            if rec.type == "prefill":
                tokens += 1
            else:
                r = self._request_of(per_dag[rec.dag_id])
                tokens += min(DECODE_UNIT,
                              r.output_len - DECODE_UNIT * (rec.tao_id - 1))
        inf = float("inf")
        ttft = [prefill_end[did] - st.arrival if did in prefill_end else inf
                for did, st in per_dag.items()]
        sojourn = [st.sojourn if st.done else inf for st in per_dag.values()]
        self.queue_delays = [st.queue_delay for st in per_dag.values()
                             if st.done]
        first, last = self.wait_trend()
        self.ctx.log(f"mean sojourn, first quarter of the requests "
                     f"{first!r} s, last quarter {last!r} s")
        return {"attempted": len(per_dag),
                "failed": sum(1 for st in per_dag.values() if not st.done),
                "metrics": {"ttft_p90_s": percentile(ttft, 90),
                            "sojourn_p90_s": percentile(sojourn, 90),
                            "tokens_per_s": tokens / seconds}}

    def wait_trend(self) -> tuple:
        """Mean sojourn of the first and of the last quarter of the
        requests, in order of arrival: a wait that grows means the offered
        rate is above what the system sustains."""
        sts = sorted(self.stats.result.per_dag.values(),
                     key=lambda st: st.arrival)
        waits = [st.sojourn if st.done else float("inf") for st in sts]
        q = max(1, len(waits) // 4)
        return sum(waits[:q]) / q, sum(waits[-q:]) / q

    # -- what per-layer readers read ----------------------------------------
    def prefill_work(self) -> work.Work:
        flops = sum(work.mamba2_prefill(self.cfg, L).flops
                    for L in self.prefill_lengths)
        return work.Work(flops=flops, bytes=0.0)

    def decode_work(self) -> work.Work:
        return work.mamba2_decode(
            self.cfg, param_bytes=jnp.dtype(self.cfg["param_dtype"]).itemsize)

    # -- correctness ------------------------------------------------------------
    def release(self) -> None:
        """Drop the program's state: the reference runs on the chip alone."""
        self.params = self.prompts = self.rt = None
        self.prefill = self.decode = None
        for s in self.served.values():
            s.cache = None

    def sample(self) -> list:
        """Finished requests to compare, drawn from the seed: the longest
        (prompt plus output) and ``check_requests - 1`` more."""
        done = [r for r in self.reqs if r.id in self.served
                and len(self.served[r.id].tokens) == r.output_len + 1]
        if not done:
            return []
        longest = max(done, key=lambda r: (r.prompt_len + r.output_len,
                                           r.id))
        rest = [r for r in done if r is not longest]
        rng = random.Random(self.ctx.seed)
        k = min(len(rest), int(self.mix["check_requests"]) - 1)
        return [longest] + rng.sample(rest, k)

    def served_tokens(self, r) -> np.ndarray:
        return np.concatenate([np.asarray(t).reshape(-1)
                               for t in self.served[r.id].tokens])

    def compare(self, control: bool = False) -> dict:
        """Widest gap of a served token below the reference's best, over the
        sample (``program``), and the number of tokens compared.  With
        ``control``, also the widest gap of the token that the fp8
        reference puts first at the same positions (``control``)."""
        w = make_weights(self.cfg, self.ctx.seed)
        prompts = make_prompts(self.reqs, self.ctx.seed,
                               int(self.cfg["vocab_size"]))
        out = {"program": 0.0, "control": 0.0, "tokens": 0}
        for r in self.sample():
            toks = self.served_tokens(r)
            # padded at the end to one length per prompt length, so that
            # the reference compiles once per length; it is causal, so the
            # padding changes no row that is read
            pad = int(self.mix["output_max"]) - r.output_len
            seq = np.concatenate([np.asarray(prompts[r.id]).reshape(-1),
                                  toks[:-1], np.zeros(pad, np.int64)])
            rows = np.arange(r.prompt_len - 1, r.prompt_len + r.output_len)
            ref = reference_logits(w, self.cfg, seq, rows)
            out["program"] = max(out["program"],
                                 float(served_gaps(ref, toks).max()))
            out["tokens"] += len(toks)
            if control:
                low = reference_logits(w, self.cfg, seq, rows, fp8=True)
                out["control"] = max(out["control"], float(
                    served_gaps(ref, low.argmax(-1)).max()))
        return out

    def checks(self) -> list:
        from yardstick.checks import Check
        res = self.stats.result
        unfinished = sum(1 for st in res.per_dag.values() if not st.done)
        wrong_count = sum(
            1 for r in self.reqs
            if r.id not in self.served
            or len(self.served[r.id].tokens) != r.output_len + 1)
        cmp = self.compare(control=self.ctx.control)
        n = cmp["tokens"]
        self.ctx.log(f"compared {n} served tokens of "
                     f"{len(self.sample())} requests with the reference")
        if self.ctx.control:
            self.ctx.log(f"program reading served_logit_gap_max "
                         f"{cmp['program']!r}; the check reads the control")
        gap = cmp["control" if self.ctx.control else "program"]
        return [
            Check("requests_unfinished", float(unfinished), 0.0),
            Check("requests_with_wrong_token_count", float(wrong_count), 0.0),
            Check("tokens_compared_short", float(n == 0), 0.0),
            Check("served_logit_gap_max", gap,
                  float(self.cfg["limits"]["served_logit_gap_max"])),
        ]
