"""The masked Median kernel (``kernels/cwise_median`` ``masked_median_views``)
and the route the protocol's pull and DMC gather take to it.

The kernel runs in interpret mode on the CPU at small shapes. Its views
must equal the jnp route, ``vmap(rules.masked_coordinate_median)`` then the
cast, element for element: the one difference allowed is a zero's sign."""
import os
import subprocess
import sys
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.agg as agg
from repro.agg import dispatch, rules
from repro.configs.paper_models import make_mlp_problem
from repro.core import protocol
from repro.data.pipeline import DeviceBatchStream, MixtureSpec
from repro.kernels.cwise_median import ops
from repro.launch.mesh import make_protocol_mesh, use_mesh
from repro.optim.schedules import inverse_linear

# what a Byzantine sender may put in a coordinate
SPECIAL = np.array([np.nan, np.inf, -np.inf, 3.4e38, -3.4e38, -0.0, 0.0],
                   np.float32)


def stack(G, body, seed=0):
    """A [G, *body] float32 replica stack: normal values, with sender 0 (a
    Byzantine one) sending every special payload and sender 1 some."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((G,) + body).astype(np.float32)
    flat = x.reshape(G, -1)
    n = flat.shape[1]
    flat[0, rng.integers(0, n, 4 * len(SPECIAL))] = np.repeat(SPECIAL, 4)
    flat[1, rng.integers(0, n, len(SPECIAL))] = SPECIAL
    return jnp.asarray(x)


def mask_sets(G, seed=0):
    """All-true masks; masks in which receiver r gets q = r % G + 1 senders
    (every q from 1 to G); and random ragged masks with q >= 1."""
    rng = np.random.default_rng(seed)
    full = np.ones((G, G), bool)
    ragged_q = np.zeros((G, G), bool)
    for r in range(G):
        ragged_q[r, rng.permutation(G)[:r % G + 1]] = True
    rand = rng.random((G, G)) < 0.5
    rand[np.arange(G), rng.integers(0, G, G)] = True
    return [jnp.asarray(m) for m in (full, ragged_q, rand)]


def jnp_route(x, masks, out_dtype):
    xf = x.astype(jnp.float32)
    return jax.vmap(lambda m: rules.masked_coordinate_median(xf, m))(
        masks).astype(out_dtype)


def assert_same(got, want):
    """Equal element for element as float32 (NaN equal to NaN, and a zero
    equal to a zero of either sign), in the same dtype and shape."""
    assert got.dtype == want.dtype and got.shape == want.shape
    a = np.asarray(got.astype(jnp.float32))
    b = np.asarray(want.astype(jnp.float32))
    same = (a == b) | (np.isnan(a) & np.isnan(b))
    assert same.all(), f"{(~same).sum()} of {same.size} differ"


# leaves of rank 2 to 4 with C a multiple of 128: with 16-row tiles
# (tile_bytes=1 gives the least) 40 and 72 rows leave a ragged edge tile
BODIES = [(384,), (40, 256), (3, 24, 128)]


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("body", BODIES, ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("G", [3, 4, 5, 8])
def test_kernel_equals_jnp_route(G, body, out_dtype):
    x = stack(G, body, seed=G)
    for masks in mask_sets(G, seed=G):
        got = ops.masked_median_views(x, masks, out_dtype, interpret=True,
                                      tile_bytes=1)
        assert_same(got, jnp_route(x, masks, out_dtype))


def test_kernel_ragged_lane_tile():
    """A minor dim wider than a tile holds and no multiple of 128: the last
    lane tile is ragged."""
    x = stack(4, (2, 4100), seed=1)
    for masks in mask_sets(4, seed=1):
        got = ops.masked_median_views(x, masks, jnp.bfloat16,
                                      interpret=True)
        assert_same(got, jnp_route(x, masks, jnp.bfloat16))


@pytest.mark.parametrize("rows,cols,out_bytes,block,sub", [
    # phi4_mini_cut's leaves at G=4, float32 senders
    (6144, 8192, 2, (32, 4096), (16, 512)),
    (16384, 3072, 2, (48, 3072), (16, 512)),
    (16384, 3072, 4, (32, 3072), (16, 512)),
    (2, 3072, 2, (2, 3072), (2, 512)),
    (1, 3072, 4, (1, 3072), (1, 512)),
    # a minor dim no multiple of 128: whole up to the lane limit, else cut
    (40, 100, 4, (40, 100), (8, 100)),
    (2, 4100, 4, (2, 4096), (2, 512)),
])
def test_tiles_fit_the_budget(rows, cols, out_bytes, block, sub):
    got = ops._masked_tiles(rows, cols, 4, 4, 4, out_bytes, ops._TILE_BYTES)
    assert got == (block, sub)
    br, bc = block
    assert 2 * br * bc * (4 * 4 + 4 * out_bytes) <= ops._TILE_BYTES


def test_dispatch_resolves_and_records():
    x, (masks, *_) = stack(5, (16, 128)), mask_sets(5)
    dispatch.resolved_backends(reset=True)
    ker = dispatch.masked_median_views(x, masks, jnp.bfloat16,
                                       backend="pallas", interpret=True)
    ref = dispatch.masked_median_views(x, masks, jnp.bfloat16, backend="jnp")
    assert_same(ker, ref)
    assert_same(ref, jnp_route(x, masks, jnp.bfloat16))
    assert dispatch.resolved_backends(reset=True) == {
        "masked_median": ["jnp", "pallas"]}


def test_dispatch_jnp_side_is_the_fallback():
    x, (masks, *_) = stack(4, (8, 128)), mask_sets(4)
    mark = jnp.zeros((4, 8, 128), jnp.float32)
    got = dispatch.masked_median_views(x, masks, jnp.float32,
                                       fallback=lambda _: mark, backend="jnp")
    assert got is mark
    got = dispatch.masked_median_views(x, masks, jnp.float32,
                                       fallback=lambda _: mark,
                                       backend="pallas", interpret=True)
    assert_same(got, jnp_route(x, masks, jnp.float32))


def test_dispatch_keeps_jnp_where_the_network_does_not_sort():
    """With the sorting network off (``jnp.sort``, whose NaNs sort last as
    NaN) or past its size, auto keeps the jnp side and an explicit pallas
    raises."""
    x, (masks, *_) = stack(4, (8, 128)), mask_sets(4)
    with rules.use_sort_network(False):
        assert_same(dispatch.masked_median_views(x, masks, jnp.float32),
                    jnp_route(x, masks, jnp.float32))
        with pytest.raises(ValueError, match="not supported"):
            dispatch.masked_median_views(x, masks, jnp.float32,
                                         backend="pallas")
    big = jnp.zeros((33, 8, 128))
    with pytest.raises(ValueError, match="not supported"):
        dispatch.masked_median_views(big, jnp.ones((2, 33), bool),
                                     jnp.float32, backend="pallas")


def test_median_alone_has_masked_views():
    assert [s.name for s in agg.specs() if s.masked_views is not None] == [
        "median"]
    assert agg.get("median").masked_views is dispatch.masked_median_views


# -- the protocol's route ----------------------------------------------------

def replica_tree(G=4):
    """Leaves of rank 1 to 4; with chunk_bytes 4096 the streamed route
    takes each of its streaming branches."""
    return {"layers": {"w": stack(G, (3, 24, 128), 1),
                       "norm": stack(G, (3, 128), 2)},
            "table": stack(G, (64, 256), 3), "scale": stack(G, (256,), 4),
            "bias": stack(G, (), 5)}


PCFG = protocol.ProtocolConfig(
    n_groups=4, f_workers=1, f_servers=0, q_workers=3, q_servers=4,
    chunk_bytes=4096)


def pulled(backend, mesh, dtype):
    """masked_pull's views of the replica tree under ragged masks: the
    pull's (float32 leaves in ``dtype``) or, with no dtype, the gather's."""
    def f(params, masks):
        if dtype is None:
            return protocol.masked_pull(params, masks, PCFG, mesh,
                                        rule=PCFG.gather_gar)
        with protocol.views_in(dtype):
            return protocol.masked_pull(params, masks, PCFG, mesh)

    with dispatch.backend_override(backend):
        return jax.jit(f)(replica_tree(), mask_sets(4)[1])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, None], ids=["pull",
                                                             "gather"])
@pytest.mark.parametrize("one_device_mesh", [False, True],
                         ids=["no_mesh", "mesh_of_one"])
def test_masked_pull_kernel_route_equals_jnp_route(one_device_mesh, dtype):
    mesh = (make_protocol_mesh(4, devices=jax.devices()[:1])
            if one_device_mesh else None)
    dispatch.resolved_backends(reset=True)
    with use_mesh(mesh) if mesh is not None else nullcontext():
        want = pulled("jnp", mesh, dtype)
        got = pulled("pallas", mesh, dtype)
    assert dispatch.resolved_backends(reset=True)["masked_median"] == [
        "jnp", "pallas"]
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert_same(g, w)
    assert {leaf.dtype for leaf in jax.tree.leaves(got)} == {
        jnp.dtype(dtype or jnp.float32)}


def test_epochs_agree_on_both_routes():
    """A whole protocol epoch (pull, worker gradients, MDA, update, gather)
    gives the same replicas on the kernel route as on the jnp route."""
    mix = MixtureSpec(n_classes=5, dim=16, sep=2.5)
    init, loss, _ = make_mlp_problem(dim=mix.dim, hidden=32,
                                     n_classes=mix.n_classes)
    pcfg = protocol.ProtocolConfig.derive(4, T=2, f_workers=1, f_servers=0,
                                          q_workers=3, q_servers=3)
    batches = DeviceBatchStream(0, mix, 4, 8).next(4)
    out = []
    for backend in ("jnp", "pallas"):
        with dispatch.backend_override(backend):
            eng = protocol.ProtocolEngine(
                protocol.ProblemBundle(init=init, loss=loss), pcfg,
                inverse_linear(0.05, 0.01))
            state = eng.init_state(jax.random.PRNGKey(0))
            state, _ = eng.run_epoch(state, batches)
        out.append(jax.tree.leaves(state.params))
    for g, w in zip(*out):
        assert_same(g, w)


def test_four_device_mesh_keeps_the_streamed_route():
    """On four virtual devices, replicas sharded over 'rep', the compiled
    pull holds no call of the kernel even with the backend forced to
    Pallas; the same program on one of the devices does."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_AGG_BACKEND="pallas",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    runner = os.path.join(os.path.dirname(__file__),
                          "_masked_median_mesh_runner.py")
    out = subprocess.run([sys.executable, runner], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MASKED_MEDIAN_MESH_PASS" in out.stdout, out.stdout
