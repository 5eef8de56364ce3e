"""ProtocolEngine: the single-host engine as the distributed protocol's oracle.

With a G = n_workers = n_servers cluster, the protocol's scatter/gather steps
draw the same quorums as ``ByzSGDSimulator`` (same key chain, pluggable
``DeliveryModel``), so on a 1-group/1-device mesh the fused protocol epochs
must reproduce the fused single-host engine: params allclose (the collective
formulation aggregates as masked rules / Gram-weighted sums, so float
summation order differs), accuracy buffers identical, diameters allclose at a
looser tolerance (a max-minus-min of nearly-identical replicas amplifies the
last-ulp noise). Mirrors ``tests/test_engine.py``'s gather off-by-one,
chunking, and ``TraceDelivery`` (realized netsim quorums + trace wrap) cases.
"""
import contextlib

import jax
import numpy as np
import pytest

from repro.configs.paper_models import make_mlp_problem
from repro.core import protocol
from repro.core.engine import EpochEngine
from repro.core.simulator import ByzSGDConfig, ByzSGDSimulator
from repro.data.pipeline import DeviceBatchStream, MixtureSpec
from repro.launch.mesh import make_protocol_mesh, use_mesh
from repro.optim.schedules import inverse_linear

MIX = MixtureSpec(n_classes=5, dim=16, sep=2.5)
BATCH = 8
G = 5


def make_cfg(T=5):
    return ByzSGDConfig(n_workers=G, f_workers=1, n_servers=G, f_servers=1,
                        T=T)


def make_pcfg(cfg, engine="sharded"):
    return protocol.ProtocolConfig.derive(
        G, T=cfg.T, engine=engine, f_workers=cfg.f_workers,
        f_servers=cfg.f_servers, q_workers=cfg.q_workers,
        q_servers=cfg.q_servers)


def problem():
    return make_mlp_problem(dim=MIX.dim, hidden=32, n_classes=MIX.n_classes)


def eval_pair():
    return DeviceBatchStream(0, MIX, G, BATCH).eval_set(256)


def fused_run(cfg, steps, eval_set, delivery=None):
    init, loss, acc = problem()
    sim = ByzSGDSimulator(cfg, init, loss, inverse_linear(0.05, 0.01),
                          delivery=delivery)
    eng = EpochEngine(sim, acc_fn=acc, eval_set=eval_set, track_delta=True)
    state = sim.init_state(jax.random.PRNGKey(0))
    return eng.run(state, stream=DeviceBatchStream(0, MIX, G, BATCH),
                   steps=steps)


def protocol_run(cfg, steps, eval_set, delivery=None, engine="sharded",
                 mesh=None, epoch_steps=None):
    init, loss, acc = problem()
    pcfg = make_pcfg(cfg, engine)
    bundle = protocol.ProblemBundle(init=init, loss=loss)
    eng = protocol.ProtocolEngine(bundle, pcfg, inverse_linear(0.05, 0.01),
                                  mesh=mesh, delivery=delivery, acc_fn=acc,
                                  eval_set=eval_set, track_delta=True)
    state = eng.init_state(jax.random.PRNGKey(0))
    return eng.run(state, stream=DeviceBatchStream(0, MIX, G, BATCH),
                   steps=steps, epoch_steps=epoch_steps)


def assert_oracle(steps, delivery_fn=None, engine="sharded", mesh=None,
                  epoch_steps=None):
    ev = eval_pair()
    s_ref, ref = fused_run(make_cfg(), steps, ev,
                           delivery_fn() if delivery_fn else None)
    s_pro, pro = protocol_run(make_cfg(), steps, ev,
                              delivery_fn() if delivery_fn else None,
                              engine=engine, mesh=mesh,
                              epoch_steps=epoch_steps)
    for a, b in zip(jax.tree.leaves(s_ref.params),
                    jax.tree.leaves(s_pro.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    assert int(s_pro.t) == steps
    np.testing.assert_allclose(ref["acc"], pro["acc"], rtol=1e-5, atol=1e-6)
    # diameters are max-minus-min over nearly-identical replicas: the ~1e-7
    # per-step aggregation noise is relatively amplified there, especially
    # right after a gather collapses the spread to ~1e-2
    np.testing.assert_allclose(ref["delta"], pro["delta"],
                               rtol=5e-2, atol=5e-3)
    np.testing.assert_allclose(ref["l2_diam"], pro["l2_diam"],
                               rtol=5e-2, atol=5e-3)
    return ref, pro


class TestOracleEquivalence:
    def test_partial_tail_epoch(self):
        # 12 = 2 full T=5 epochs (gathers after steps 5 and 10) + 2 tail steps
        ref, pro = assert_oracle(steps=12)
        np.testing.assert_array_equal(ref["acc"], pro["acc"])  # identical

    def test_exact_epoch_boundary(self):
        # the DMC gather fires after the LAST step: t % T == 0 at t = T
        assert_oracle(steps=5)

    def test_one_step_past_boundary(self):
        assert_oracle(steps=6)

    def test_chunking_does_not_change_results(self):
        # scan chunk length is free: the boundary rides on the carried t
        assert_oracle(steps=12, epoch_steps=7)

    def test_naive_collective_engine(self):
        assert_oracle(steps=12, engine="naive")

    def test_one_device_mesh(self):
        # the acceptance path: protocol on a ('rep','fsdp','model') mesh over
        # the available devices (1-device here) still matches the oracle
        mesh = make_protocol_mesh(G)
        assert mesh.devices.shape == (1, 1, 1)
        with use_mesh(mesh):
            assert_oracle(steps=12, mesh=mesh)

    def test_stepwise_loop_is_also_the_oracle(self):
        # protocol == fused == stepwise: close the triangle via the per-step
        # reference loop
        ev = eval_pair()
        init, loss, acc = problem()
        cfg = make_cfg()
        sim = ByzSGDSimulator(cfg, init, loss, inverse_linear(0.05, 0.01))
        state = sim.init_state(jax.random.PRNGKey(0))
        from repro.data.pipeline import classification_stream
        stream, _ = classification_stream(0, MIX, G, BATCH, 12)
        state, _ = sim.run(state, stream)
        s_pro, _ = protocol_run(cfg, 12, ev)
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(s_pro.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)


def trace_delivery():
    from repro.netsim import ClusterSim, scenarios
    sc = scenarios.build("heavy_tail_stragglers", n_workers=G, f_workers=1,
                         n_servers=G, f_servers=1, T=5, steps=10,
                         model_d=1000)
    trace = ClusterSim(sc).run()
    # masked delivery collapses duplicate sender ids; the realized quorums of
    # a shortfall-free run are duplicate-free, which is what makes the
    # masked-protocol and subset-simulator paths aggregate the same stacks
    assert trace.shortfalls == 0
    return trace.to_delivery()


class TestTraceDelivery:
    def test_protocol_on_realized_quorums_matches_fused(self):
        assert_oracle(steps=10, delivery_fn=trace_delivery)

    def test_run_past_trace_length_wraps(self):
        # trace has 10 steps; a 14-step run wraps (t mod trace length) in
        # both paths, crossing a gather boundary on the wrapped counter
        assert_oracle(steps=14, delivery_fn=trace_delivery)

    def test_gather_round_indexing(self):
        # steps == 2T exactly: the second gather reads trace round r=1, the
        # off-by-one mirrored from tests/test_engine.py
        assert_oracle(steps=10, delivery_fn=trace_delivery, epoch_steps=4)


class TestEngineMechanics:
    def test_compile_cache_shared_across_instances(self):
        init, loss, _ = problem()
        pcfg = make_pcfg(make_cfg())
        bundle = protocol.ProblemBundle(init=init, loss=loss)
        a = protocol.ProtocolEngine(bundle, pcfg, inverse_linear(0.05, 0.01))
        init2, loss2, _ = problem()  # fresh partials, same semantics
        b = protocol.ProtocolEngine(
            protocol.ProblemBundle(init=init2, loss=loss2), pcfg,
            inverse_linear(0.05, 0.01))
        assert a._epoch is b._epoch

    def test_engines_key_separately(self):
        init, loss, _ = problem()
        bundle = protocol.ProblemBundle(init=init, loss=loss)
        a = protocol.ProtocolEngine(bundle, make_pcfg(make_cfg(), "sharded"),
                                    inverse_linear(0.05, 0.01))
        b = protocol.ProtocolEngine(bundle, make_pcfg(make_cfg(), "naive"),
                                    inverse_linear(0.05, 0.01))
        assert a._epoch is not b._epoch

    @pytest.mark.parametrize("on_mesh", [False, True])
    def test_lower_reproduces_the_dispatched_program(self, on_mesh):
        # lower() rebuilds the program of the last dispatch from its
        # argument types and ambient mesh: the same module as lowering the
        # real arguments, so a persistent compile cache serves its compile
        init, loss, acc = problem()
        mesh = make_protocol_mesh(G) if on_mesh else None
        eng = protocol.ProtocolEngine(
            protocol.ProblemBundle(init=init, loss=loss),
            make_pcfg(make_cfg()), inverse_linear(0.05, 0.01), mesh=mesh,
            acc_fn=acc, eval_set=eval_pair())
        with use_mesh(mesh) if on_mesh else contextlib.nullcontext():
            state = eng.init_state(jax.random.PRNGKey(0))
            batches = DeviceBatchStream(0, MIX, G, BATCH).next(5)
            want = eng._epoch.lower(state, batches,
                                    *eng._extra_args()).as_text()
            state, _ = eng.run_epoch(state, batches)
        assert eng.lower().as_text() == want

    def test_acc_fn_requires_eval_set(self):
        init, loss, acc = problem()
        bundle = protocol.ProblemBundle(init=init, loss=loss)
        with pytest.raises(ValueError):
            protocol.ProtocolEngine(bundle, make_pcfg(make_cfg()),
                                    inverse_linear(0.05, 0.01), acc_fn=acc)

    def test_collective_volume_model(self):
        # both engines lower to (G-1)·P pull + (G-1)·P push exchanges —
        # HLO-verified by repro.analyze (REPRO-HLO-COLLECTIVES); the old
        # "sharded ≈ 2·P" model was 4x off what XLA actually compiles
        sharded = make_pcfg(make_cfg(), "sharded")
        naive = make_pcfg(make_cfg(), "naive")
        P = 10_000
        assert protocol.collective_volume_bytes(naive, P) == \
            2 * (G - 1) * P * 4
        assert protocol.collective_volume_bytes(sharded, P) == \
            protocol.collective_volume_bytes(naive, P)


@pytest.mark.parametrize("body", [(6, 24, 128), (1024, 64)],
                         ids=["layer_stack", "wide_table"])
def test_aggregate_gradients_streamed_equals_whole(body):
    """A leaf larger than ``chunk_bytes`` is aggregated in a loop: a
    [G, L, ...] layer stack one layer at a time (``_map_dim1``), a wide
    [G, V, D] table (V past the layer-stack limit) in slices of D
    (``_map_last_chunks``). The loop gives the whole leaf's weighted sums."""
    rng = np.random.default_rng(0)
    grads = {"w": rng.standard_normal((G,) + body).astype(np.float32)}
    weights = rng.dirichlet(np.ones(G), G).astype(np.float32)

    def aggregate(chunk_bytes):
        pcfg = protocol.ProtocolConfig(
            n_groups=G, f_workers=1, f_servers=1, q_workers=4, q_servers=4,
            chunk_bytes=chunk_bytes)
        fn = jax.jit(lambda g, w: protocol.aggregate_gradients(g, w, pcfg))
        jaxpr = str(jax.make_jaxpr(fn)(grads, weights))
        loops = jaxpr.count("scan[") + jaxpr.count("while[")
        return np.asarray(fn(grads, weights)["w"]), loops

    streamed, n_streamed = aggregate(4096)
    whole, n_whole = aggregate(2**40)
    assert (n_streamed, n_whole) == (1, 0)
    want = np.einsum("sw,w...->s...", weights.astype(np.float64), grads["w"])
    np.testing.assert_allclose(streamed, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(streamed, whole, rtol=1e-6, atol=1e-6)
