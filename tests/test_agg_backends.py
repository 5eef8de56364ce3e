"""Backend-equivalence tests: Pallas kernels vs pure-jnp references through
the repro.agg dispatch layer, across odd/even n and non-multiple-of-block d,
with interpret-mode fallback on CPU (auto-enabled off-TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.agg as agg

# odd/even n; d off lane (128) and block (512/1024) multiples on purpose
SHAPES = [(5, 64), (8, 127), (9, 130), (12, 513), (16, 777), (31, 1025)]


def rand(n, d, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed or n * d + 1), (n, d),
                             dtype)


@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_sqdist_backends_agree(n, d, dtype):
    x = rand(n, d, dtype=dtype)
    ref = agg.pairwise_sqdists(x, backend="jnp")
    ker = agg.pairwise_sqdists(x, backend="pallas")
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(ker, ref, rtol=tol, atol=tol * d)


@pytest.mark.parametrize("n,d", SHAPES)
def test_cwise_median_backends_agree(n, d):
    x = rand(n, d)
    ref = agg.cwise_median(x, backend="jnp")
    ker = agg.cwise_median(x, backend="pallas")
    np.testing.assert_allclose(ker, ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n,f", [(7, 2), (8, 2), (9, 2), (12, 3), (13, 4)])
def test_mda_diameter_backends_agree(n, f):
    d2 = agg.rules.pairwise_sqdists(rand(n, 50))
    masks = jnp.asarray(agg.rules.subset_masks(n, f))
    ref = agg.subset_diameters(d2, masks, backend="jnp")
    ker = agg.subset_diameters(d2, masks, backend="pallas")
    np.testing.assert_allclose(ker, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ["mda", "median", "krum", "multi_krum",
                                  "meamed", "trimmed_mean"])
@pytest.mark.parametrize("n,d", [(9, 100), (8, 127), (13, 257)])
def test_rule_backends_agree(name, n, d):
    """End-to-end: the registry rule produces the same aggregate on both
    backends for every rule that declares a pallas path."""
    spec = agg.get(name)
    assert "pallas" in spec.backends
    x = rand(n, d, seed=n + d)
    f = 2
    ref = spec(x, f, backend="jnp")
    ker = spec(x, f, backend="pallas")
    np.testing.assert_allclose(ker, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("f", [0, 1, 2])
def test_cwise_order_statistic_kernels_agree(n, d, f):
    """meamed + trimmed_mean share cwise_median's sorting network; their
    kernels must match the jnp references across odd/even n, off-block d,
    and every admissible f."""
    if n <= 2 * f:
        pytest.skip("trimmed_mean needs n > 2f")
    x = rand(n, d, seed=7 * n + d + f)
    for name in ("meamed", "trimmed_mean"):
        spec = agg.get(name)
        ref = spec(x, f, backend="jnp")
        ker = spec(x, f, backend="pallas")
        np.testing.assert_allclose(ker, ref, rtol=1e-5, atol=1e-5,
                                   err_msg=f"{name} n={n} d={d} f={f}")


def test_meamed_kernel_asymmetric_ties_match_reference():
    """Colluding duplicate payloads tie several candidate windows on max
    endpoint distance; the kernel must still pick the reference's window
    (the one with the n-f smallest distances), not a window stuffed with
    tied outliers."""
    col = jnp.asarray([0., -3., 0., 0., 1., -3., -3., -1., -3., 1.])[:, None]
    ref = agg.rules.meamed(col, 3)
    ker = agg.get("meamed")(col, 3, backend="pallas")
    np.testing.assert_allclose(ker, ref, rtol=1e-6, atol=1e-6)


def test_meamed_kernel_tie_quality_on_integer_stacks():
    """On tie-heavy integer data the kernel's selection must match the
    reference's *quality* exactly — same max distance and same distance sum
    (the quantities the robustness analysis uses). The averaged values may
    differ only when a pair sits exactly equidistant on opposite sides of
    the median (the reference breaks that tie by input position, which a
    sorted tile cannot see)."""
    rng = np.random.default_rng(0)
    for _ in range(40):
        n = int(rng.integers(3, 14))
        f = int(rng.integers(0, (n - 1) // 2 + 1))
        x = np.asarray(rng.integers(-3, 4, size=(n, 8)), np.float32)
        ker = np.asarray(agg.get("meamed")(jnp.asarray(x), f,
                                           backend="pallas"))
        m = n - f
        med = np.median(x, axis=0)
        for c in range(x.shape[1]):
            d_ref = np.sort(np.abs(x[:, c] - med[c]))[:m]
            s = np.sort(x[:, c])
            # the kernel's lexicographic (max, sum) window criterion
            cand = [(max(abs(s[i] - med[c]), abs(s[i + m - 1] - med[c])),
                     np.abs(s[i:i + m] - med[c]).sum(), s[i:i + m].mean())
                    for i in range(f + 1)]
            kmax, ksum, kmean = min(cand, key=lambda t: (t[0], t[1]))
            assert kmax == pytest.approx(d_ref.max(), abs=1e-5)
            assert ksum == pytest.approx(d_ref.sum(), abs=1e-4)
            assert ker[c] == pytest.approx(kmean, abs=1e-5)


def test_order_statistic_kernels_size_limit_falls_back():
    """auto backend falls back past n<=64 / multi-dim leaves; explicit pallas
    raises (same contract as the median kernel)."""
    x = rand(65, 32)
    np.testing.assert_allclose(agg.get("meamed")(x, 2),
                               agg.rules.meamed(x, 2), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="n <= 64"):
        agg.get("trimmed_mean")(x, 2, backend="pallas")


def test_median_kernel_size_limit_falls_back():
    """auto backend silently falls back past the kernel's n<=64 limit;
    explicit pallas raises the documented error."""
    x = rand(65, 32)
    np.testing.assert_allclose(agg.cwise_median(x), jnp.median(x, axis=0),
                               rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="n <= 64"):
        agg.cwise_median(x, backend="pallas")


def test_interpret_flag_forced():
    """interpret=True is honored (the CPU fallback the benchmarks use)."""
    x = rand(9, 130)
    got = agg.pairwise_sqdists(x, backend="pallas", interpret=True)
    np.testing.assert_allclose(got, agg.rules.pairwise_sqdists(x),
                               rtol=1e-4, atol=1e-3)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        agg.pairwise_sqdists(rand(5, 8), backend="cuda")


def test_auto_resolution_matches_platform():
    expect = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert agg.resolve_backend("auto") == expect
    assert agg.resolve_backend(None) in ("jnp", "pallas")


def test_resolved_backends_records_each_primitive():
    """What each primitive resolved to while tracing, per primitive; a
    multi-dim leaf the kernel cannot take records jnp beside pallas."""
    agg.dispatch.resolved_backends(reset=True)
    jax.jit(lambda x: agg.cwise_median(x, backend="pallas",
                                       interpret=True))(rand(5, 16))
    jax.jit(lambda x: agg.cwise_median(x))(rand(5, 4, 4))
    agg.pairwise_sqdists(rand(5, 8), backend="jnp")
    assert agg.dispatch.resolved_backends(reset=True) == {
        "cwise_median": ["jnp", "pallas"], "pairwise_sqdist": ["jnp"]}
    assert agg.dispatch.resolved_backends() == {}
