"""Plain reference of the ByzSGD protocol step (arXiv:1905.03853), the
asynchronous variant with co-located worker+server groups.

G groups each hold a server replica. One step, from the protocol key:

1. ``key, k_pull, _, k_push, _ = split(key, 5)``;
2. pull: worker g takes the coordinate-wise median of the replicas of the
   ``q_servers`` servers it delivers (a uniform subset drawn from
   ``k_pull``);
3. worker g's gradient of its rows' loss at that model;
4. server s delivers ``q_workers`` gradients (drawn from ``k_push``) and
   applies MDA: the average of the size ``q - f_workers`` subset of least
   diameter (largest pairwise squared distance);
5. SGD: ``replica_s -= lr * aggregate_s``;
6. after every T-th step, the DMC gather: ``key, k_q, _ = split(key, 3)``;
   server s takes the coordinate-wise median of ``q_servers`` replicas,
   its own among them.

A delivered subset is drawn as ByzSGD's Assumption 7 states it, uniformly:
per receiver, one key of ``split(k, G)``, uniform scores, the ``q`` lowest
(the receiver's own score set to -1 where it must deliver itself).

Everything runs group by group and leaf by leaf, so that G float32 replicas,
G gradients and one model's activations fit one chip. Selections are made on
the host; the least margin of any (the relative gap between the least and
the next diameter) is recorded, to show how decisive MDA's choices were.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Protocol:
    groups: int
    f_workers: int
    q_workers: int
    q_servers: int
    T: int
    lr: float


@dataclass
class Outcome:
    replicas: list                     # G parameter trees after the run
    first_grads: list                  # per-leaf norms of server s's
                                       # aggregate at step 0
    least_margin: float = float("inf")  # of MDA's choices: (next diameter
                                        # - least) / least


def delivered(key, n: int, q: int, include_self: bool) -> np.ndarray:
    """[n, q] sender ids each of n receivers delivers (uniform q-subsets)."""
    keys = jax.random.split(key, n)
    out = []
    for r in range(n):
        scores = jax.random.uniform(keys[r], (n,))
        if include_self:
            scores = scores.at[r].set(-1.0)
        out.append(np.asarray(jnp.argsort(scores))[:q])
    return np.stack(out)


@jax.jit
def _median(*leaves):
    return jnp.median(jnp.stack(leaves), axis=0)


@jax.jit
def _sqdist(a, b):
    return sum(jnp.sum((x - y) ** 2) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@jax.jit
def _step(params, weights, lr, *grads):
    agg = jax.tree.map(lambda *gs: sum(w * g for w, g in zip(weights, gs)),
                       *grads)
    return jax.tree.map(lambda p, g: p - lr * g, params, agg), agg


def _median_of(trees):
    return jax.tree.map(_median, *trees)


def run(p0, batches, key, proto: Protocol, grad_fn, *,
        faults=()) -> Outcome:
    """Run ``len(batches)`` steps from G copies of ``p0``.

    ``batches[t]`` holds ``tokens`` and ``labels`` of shape [G, b, S];
    ``grad_fn(params, tokens, labels)`` is the jitted gradient of the
    reference loss. ``faults`` plants the
    faults a check must catch: ``"no_exchange"`` (each group pulls,
    aggregates and gathers its own replica and gradient only) and
    ``"half_batch"`` (each gradient over the first half of each row)."""
    G, f = proto.groups, proto.f_workers
    P = [p0] * G
    out = Outcome(replicas=[], first_grads=[])
    for t, batch in enumerate(batches):
        key, k_pull, _, k_push, _ = jax.random.split(key, 5)
        pulls = delivered(k_pull, G, proto.q_servers, False)
        grads = []
        for g in range(G):
            src = [g] if "no_exchange" in faults else list(pulls[g])
            model = _median_of([P[i] for i in src]) if len(src) > 1 else P[g]
            tok, lab = batch["tokens"][g], batch["labels"][g]
            if "half_batch" in faults:
                half = tok.shape[-1] // 2
                tok, lab = tok[..., :half], lab[..., :half]
            grads.append(grad_fn(model, tok, lab))
        d2 = np.zeros((G, G))
        for i, j in itertools.combinations(range(G), 2):
            d2[i, j] = d2[j, i] = float(_sqdist(grads[i], grads[j]))
        pushes = delivered(k_push, G, proto.q_workers, False)
        newP = []
        for s in range(G):
            idx = [s] if "no_exchange" in faults else list(pushes[s])
            keep = max(len(idx) - f, 1) if len(idx) > 1 else 1
            subsets = [list(c) for c in itertools.combinations(idx, keep)]
            diam = [max((d2[a, b] for a, b in
                         itertools.combinations(sub, 2)), default=0.0)
                    for sub in subsets]
            order = sorted(range(len(subsets)), key=lambda i: diam[i])
            if len(subsets) > 1:
                least, nxt = diam[order[0]], diam[order[1]]
                out.least_margin = min(out.least_margin,
                                       (nxt - least) / max(least, 1e-30))
            chosen = subsets[order[0]]
            w = np.zeros(G, np.float32)
            w[chosen] = 1.0 / len(chosen)
            new, agg = _step(P[s], jnp.asarray(w), np.float32(proto.lr),
                             *grads)
            newP.append(new)
            if t == 0:
                out.first_grads.append(jax.tree.map(
                    lambda a: float(jnp.linalg.norm(a)), agg))
        del grads
        P = newP
        if (t + 1) % proto.T == 0:
            key, k_q, _ = jax.random.split(key, 3)
            gathers = delivered(k_q, G, proto.q_servers, True)
            P = [P[s] if "no_exchange" in faults
                 else _median_of([P[i] for i in gathers[s]])
                 for s in range(G)]
    out.replicas = P
    return out
