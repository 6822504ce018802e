"""Layered random DAGs of the paper's evaluation (arXiv:1901.05907 §4.3).

Copied from the program's ``core/dag_gen.random_dag`` (Topcuoglu-style
layered DAGs, equal shares of matmul, sort and copy TAOs), so the traffic
cannot change when the program does.  It returns plain data: the type of
each node and the indices of its parents, in an order in which every
parent precedes its children.  Parallelism degree is ``#TAOs / Cp``, and
``Cp`` equals the number of layers because every node has a parent in the
layer before it.
"""
from __future__ import annotations

import dataclasses
import random
from typing import Sequence

KERNEL_TYPES = ("matmul", "sort", "copy")


@dataclasses.dataclass(frozen=True)
class DagShape:
    types: tuple            # node i's kernel type
    parents: tuple          # node i's parents, as node indices
    n_layers: int

    @property
    def degree(self) -> float:
        return len(self.types) / self.n_layers


def random_dag(n_tasks: int, target_degree: float,
               kernel_types: Sequence[str] = KERNEL_TYPES, seed: int = 0,
               max_extra_parents: int = 2, jump_prob: float = 0.15,
               max_jump: int = 3) -> DagShape:
    """Layer widths ~ Uniform{1, .., 2*target_degree-1}; every node of layer
    i+1 has a parent in layer i, up to ``max_extra_parents`` more there,
    and with ``jump_prob`` one from up to ``max_jump`` layers further up."""
    if target_degree < 1.0:
        raise ValueError("target_degree must be >= 1")
    rng = random.Random(seed)
    widths: list[int] = []
    total = 0
    hi = max(1, int(round(2 * target_degree - 1)))
    while total < n_tasks:
        w = min(rng.randint(1, hi), n_tasks - total)
        widths.append(w)
        total += w

    types: list[str] = []
    base, rem = divmod(n_tasks, len(kernel_types))
    for i, kt in enumerate(kernel_types):
        types.extend([kt] * (base + (1 if i < rem else 0)))
    rng.shuffle(types)

    layers: list[list[int]] = []
    nxt = 0
    for w in widths:
        layers.append(list(range(nxt, nxt + w)))
        nxt += w
    parents: list[list[int]] = [[] for _ in range(n_tasks)]
    for li in range(1, len(layers)):
        prev = layers[li - 1]
        for node in layers[li]:
            ps = [rng.choice(prev)]
            for _ in range(rng.randint(0, max_extra_parents)):
                p = rng.choice(prev)
                if p not in ps:
                    ps.append(p)
            if li >= 2 and rng.random() < jump_prob:
                src = layers[max(0, li - 1 - rng.randint(1, max_jump))]
                p = rng.choice(src)
                if p not in ps:
                    ps.append(p)
            parents[node] = ps
    return DagShape(types=tuple(types),
                    parents=tuple(tuple(p) for p in parents),
                    n_layers=len(layers))
