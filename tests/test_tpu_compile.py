"""Every Pallas kernel compiles for a described TPU v5e chip.

Interpret mode (the other kernel tests) cannot see the TPU's tiling rules or
its VMEM budget; the chip's compiler, which is installed here, refuses both
for a chip that is described and not attached. Each test lowers its kernel
with ``interpret=False`` against one chip of a ``v5e:2x2`` topology and
compiles it; nothing runs. The topology is described inside a module fixture
(only the test process that is handed this file loads the TPU library), and
every kernel test lives in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.agg import rules
from repro.kernels.cwise_median import ops as med_ops
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mda_diameter import ops as md_ops
from repro.kernels.pairwise_sqdist import ops as gram_ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-chip executable is written to the persistent cache but can
    # never be read back without the chip; keep the cache out of these tests
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Lower and compile for the described chip; the compiled text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (B, S, H, kvH, hd, q_block, kv_block): the reduced configs' 64/64 blocks and
# the production blocks at phi4-mini-3.8b's published head widths
_FLASH = [(2, 256, 4, 2, 32, 64, 64),
          (1, 2048, 24, 8, 128, 512, 1024)]


@pytest.mark.parametrize("B,S,H,kvH,hd,qb,kb", _FLASH)
def test_flash_forward_compiles(one_chip, B, S, H, kvH, hd, qb, kb):
    q = _sds((B, S, H, hd), jnp.bfloat16, one_chip)
    kv = _sds((B, S, kvH, hd), jnp.bfloat16, one_chip)
    text = _compile(lambda q, k, v: flash_attention(
        q, k, v, q_block=qb, kv_block=kb, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,S,H,kvH,hd,qb,kb", _FLASH)
def test_flash_backward_compiles(one_chip, B, S, H, kvH, hd, qb, kb):
    q = _sds((B, S, H, hd), jnp.bfloat16, one_chip)
    kv = _sds((B, S, kvH, hd), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        o = flash_attention(q, k, v, q_block=qb, kv_block=kb,
                            interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # forward (recomputed under grad), dq and dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


def _protocol_mesh_2x2(topo):
    return Mesh(np.asarray(topo.devices).reshape(4, 1, 1),
                ("rep", "fsdp", "model"))


@pytest.fixture
def mesh_flash(monkeypatch):
    """``models.layers``' flash path, the one that runs per shard under a
    mesh, with the kernels lowered for the chip (not interpreted)."""
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.models import layers
    monkeypatch.setattr(flash_ops, "_default_interpret", lambda: False)
    return layers._flash_attention


def test_flash_backward_compiles_on_2x2_protocol_mesh(topo, mesh_flash):
    """Four groups, one per chip: the worker gradient is a vmap over 'rep'
    under the protocol's activation rules. XLA cannot partition a Mosaic
    kernel, so flash must run per shard."""
    from repro.launch.mesh import use_mesh
    from repro.models.sharding import sharding_rules
    mesh = _protocol_mesh_2x2(topo)
    rep = NamedSharding(mesh, PartitionSpec("rep"))
    heads = NamedSharding(mesh, PartitionSpec("fsdp", None, "model", None))
    q = _sds((4, 1, 256, 4, 32), jnp.bfloat16, rep)
    kv = _sds((4, 1, 256, 2, 32), jnp.bfloat16, rep)

    def loss(q, k, v):
        o = mesh_flash(q, k, v, causal=True, window=0, q_block=64,
                       kv_block=64)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with use_mesh(mesh), sharding_rules({"act_heads": heads,
                                         "act_kv_heads": heads}):
        text = _compile(jax.vmap(jax.grad(loss, argnums=(0, 1, 2)),
                                 spmd_axis_name="rep"), q, kv, kv)
    assert text.count("tpu_custom_call") >= 3


def test_flash_eval_compiles_on_2x2_protocol_mesh(topo, mesh_flash):
    """The protocol runner's final eval: one replica's forward, not
    vmapped and with no activation rules installed, on arrays spread over
    the run's mesh (at production blocks)."""
    from repro.launch.mesh import use_mesh
    mesh = _protocol_mesh_2x2(topo)
    rep = NamedSharding(mesh, PartitionSpec("rep"))
    q = _sds((4, 2048, 24, 128), jnp.bfloat16, rep)
    kv = _sds((4, 2048, 8, 128), jnp.bfloat16, rep)
    with use_mesh(mesh):
        text = _compile(lambda q, k, v: mesh_flash(
            q, k, v, causal=True, window=0, q_block=512, kv_block=1024),
            q, kv, kv)
    assert "tpu_custom_call" in text


_N, _D = 5, 2 ** 20


@pytest.mark.parametrize("rule", ["median", "meamed", "trimmed_mean"])
def test_cwise_rules_compile(one_chip, rule):
    x = _sds((_N, _D), jnp.float32, one_chip)
    fns = {"median": lambda x: med_ops.cwise_median(x, interpret=False),
           "meamed": lambda x: med_ops.cwise_meamed(x, 1, interpret=False),
           "trimmed_mean": lambda x: med_ops.cwise_trimmed_mean(
               x, 1, interpret=False)}
    assert "tpu_custom_call" in _compile(fns[rule], x)


def test_gram_compiles(one_chip):
    x = _sds((_N, _D), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compile(
        lambda x: gram_ops.gram(x, interpret=False), x)


@pytest.mark.parametrize("n,f", [(5, 1), (13, 4)])
def test_subset_diameters_compile(one_chip, n, f):
    s = len(rules.subset_masks(n, f))
    d2 = _sds((n, n), jnp.float32, one_chip)
    masks = _sds((s, n), jnp.bool_, one_chip)
    assert "tpu_custom_call" in _compile(
        lambda d2, m: md_ops.subset_diameters(d2, m, interpret=False),
        d2, masks)
