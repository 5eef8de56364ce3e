"""The comparison that decides ``correct``.

The window's entry is ``run_epoch``; set-up drives the same engine through
its first epoch (T steps on the pool's first rows, the DMC gather at its
end) and keeps the G replicas it produced. After the window, the plain
reference (``reference/byzsgd.py`` over the configuration's model) follows
the same T steps from the same weights, rows and protocol key, in float32
at the highest matmul precision. Three numbers over replicas and leaves,
with ``dp``/``dr`` the program's and the reference's change of a leaf over
the epoch and ``scale = max(|dr|, median leaf's |dr|)``:

- ``upd_norm_gap``: the worst ``| |dp| - |dr| | / scale``;
- ``upd_diff``: the worst ``|dp - dr| / scale``;
- ``upd_diff_median``: the median ``|dp - dr| / scale``. The worst leaf is
  the tied embedding table, whose gradient the program accumulates in
  bfloat16; the median leaf is steady from seed to seed and is what a fault
  that touches every leaf (a step's rows halved, the exchange left out)
  moves.

Leaves whose first aggregated gradient in the reference is under a
thousandth of the median leaf's are left out: they move by round-off alone.
The reference runs once, and its outcome is the one judged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("upd_norm_gap", "upd_diff", "upd_diff_median")
MOVED_FLOOR = 1e-3


@jax.jit
def _norms(prog, ref, p0):
    dp, dr = prog - p0, ref - p0
    return jnp.linalg.norm(dp), jnp.linalg.norm(dr), jnp.linalg.norm(dp - dr)


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def compare(prog: list[list[np.ndarray]], outcome, p0) -> dict:
    """Numbers comparing the program's replicas (host leaf lists) with the
    reference's ``outcome`` from the same initial weights ``p0``."""
    names = leaf_names(p0)
    base = jax.tree.leaves(p0)
    rows, excluded = [], set()
    for g, (mine, ref) in enumerate(zip(prog, outcome.replicas)):
        g1 = np.asarray(jax.tree.leaves(outcome.first_grads[g]))
        moved = g1 >= MOVED_FLOOR * np.median(g1)
        excluded |= {n for n, m in zip(names, moved) if not m}
        norms = [(n, *(float(x) for x in _norms(jnp.asarray(a), b, c)))
                 for n, a, b, c, m in zip(names, mine,
                                          jax.tree.leaves(ref), base, moved)
                 if m]
        med = float(np.median([r[2] for r in norms]))
        for name, n_p, n_r, n_d in norms:
            scale = max(n_r, med)
            rows.append((f"replica {g} {name}", abs(n_p - n_r) / scale,
                         n_d / scale))
    gap = np.array([r[1] for r in rows])
    diff = np.array([r[2] for r in rows])

    def worst(v):
        i = int(np.argmax(np.where(np.isnan(v), np.inf, v)))
        return float(v[i]), rows[i][0]

    (g_val, g_at), (d_val, d_at) = worst(gap), worst(diff)
    return {"upd_norm_gap": g_val, "upd_diff": d_val,
            "upd_diff_median": float(np.median(diff)),
            "worst": {"upd_norm_gap": g_at, "upd_diff": d_at},
            "excluded": sorted(excluded), "leaves": rows}


def reference(cell, seed: int, device, faults=()):
    """``(p0, outcome)``: the weights ``p0`` of ``seed`` and the plain
    reference's ``reference.byzsgd.Outcome`` of ``cell``'s first epoch from
    them, run on ``device``; ``faults`` as ``byzsgd.run`` takes them."""
    from benchlib import traffic
    from reference import byzsgd
    conf, exp = cell.config, cell.settings["experiment"]
    if exp["schedule"] != "constant":
        raise ValueError("the reference follows a constant learning rate")
    T, G = exp["T"], exp["n_workers"]
    proto = byzsgd.Protocol(groups=G, f_workers=exp["f_workers"],
                            q_workers=exp["q_workers"],
                            q_servers=exp["q_servers"], T=T, lr=exp["lr0"])
    ref = cell.reference
    k_model, k_run, k_rows = traffic.run_keys(seed)
    with jax.default_device(device):
        p0 = jax.jit(lambda k: ref.init_params(k, conf))(k_model)
        rows = traffic.make_pool(k_rows, cell.traffic,
                                 vocab=conf["vocab_size"], T=T, groups=G)[0]
    batches = [{k: v[t] for k, v in rows.items()} for t in range(T)]
    grad_fn = jax.jit(jax.grad(lambda p, x, y: ref.loss(p, x, y, conf)))
    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        return p0, byzsgd.run(p0, batches, k_run, proto, grad_fn,
                              faults=faults)


def passes(numbers: dict, limits: dict) -> bool:
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
