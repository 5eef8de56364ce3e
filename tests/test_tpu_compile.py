"""Every Pallas kernel compiles for a described TPU v5e chip, and the Finch
WKV scan's backward keeps one state per chunk there.

Interpret mode (the other kernel tests) cannot see the TPU's tiling rules or
its VMEM budget; the chip's compiler, which is installed here, refuses both
for a chip that is described and not attached. Each test lowers its kernel
with ``interpret=False`` against one chip of a ``v5e:2x2`` topology and
compiles it; nothing runs. The topology is described inside a module fixture
(only the test process that is handed this file loads the TPU library), and
every kernel test lives in this one file.
"""
import os
import re

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


# phi4_mini_cut's leaves at G=4: an MLP matrix of the two scanned layers,
# the embedding table, the layers' norm scales
@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["pull", "gather"])
@pytest.mark.parametrize("shape", [(4, 2, 3072, 8192), (4, 16384, 3072),
                                   (4, 2, 3072)],
                         ids=lambda s: "x".join(map(str, s)))
def test_masked_median_compiles(one_chip, shape, out_dtype):
    """The pull's views in bfloat16 and the gather's in float32, within the
    default scoped VMEM. The leaf goes to the kernel as it is, and the
    gather's views overwrite replicas that are dead after it: no copy."""
    x = _sds(shape, jnp.float32, one_chip)
    masks = _sds((4, 4), jnp.bool_, one_chip)
    text = jax.jit(lambda x, m: med_ops.masked_median_views(
        x, m, out_dtype, interpret=False), donate_argnums=0).lower(
            x, masks).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "masked_median" in line]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert "copy(" not in text and "transpose(" not in text


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


def _hlo_computations(text):
    """The instruction lines of each computation of a compiled module's
    text, by computation name."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            cur = None
            if line.endswith("{"):
                cur = line.split(" ", 2)[
                    1 if line.startswith("ENTRY ") else 0].lstrip("%")
                comps[cur] = []
        elif cur is not None and " = " in line:
            comps[cur].append(line)
    return comps


def _result_dims(line):
    """The dimensions of each array in an instruction's result."""
    result = re.match(r"\s*(?:ROOT )?\S+ = (.*?) [\w-]+\(", line).group(1)
    return [tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"\w+\[([\d,]+)\]", result)]


def _called(comps, roots):
    """The computations ``roots`` run, themselves included."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                    line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def test_finch_wkv_backward_keeps_one_state_per_chunk(one_chip):
    """One Finch time-mix layer at the rwkv6-g4-s2k cell's widths (D 2048,
    32 heads of 64, 2048 tokens, G=4 replicas), forward and backward. The
    chunk-parallel WKV computes every chunk's [C,C,K] pairwise decays inside
    the reductions that read them, forward and backward, so no buffer
    outside a fusion holds that tensor for all 128 chunks (1.07 GB at these
    shapes, twice that padded); and its loop over the chunks holds no
    pairwise op ([..,16,16,..] or [..,16,64,16]), only the [H,K,V] state
    and each chunk's own rows."""
    from repro.models import rwkv6
    from repro.models.registry import get_bundle
    cfg = get_bundle("rwkv6-1.6b", n_layers=1, vocab=8192).cfg
    G, S, D = 4, 2048, cfg.d_model
    blk = jax.eval_shape(jax.vmap(lambda k: rwkv6.init_block(k, cfg)),
                         jax.random.split(jax.random.PRNGKey(0), G))
    blk = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), blk)
    x = _sds((G, 1, S, D), jnp.bfloat16, one_chip)

    def loss(p, x):
        out = jax.vmap(lambda p, x: rwkv6.time_mix(
            p, x, cfg, jnp.bfloat16, None)[0])(p, x)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss)).lower(blk, x).compile()
    comps = _hlo_computations(compiled.as_text())
    chunks, chunk = S // 16, 16
    fused = {c for lines in comps.values() for line in lines
             for c in re.findall(r"calls=%([\w.\-]+)", line)}
    for c in set(comps) - fused:
        for line in comps[c]:
            for dims in _result_dims(line):
                assert not (dims.count(chunk) >= 2 and chunks in dims
                            and 64 in dims), line
    bodies = {b for lines in comps.values() for line in lines
              for b in re.findall(r"body=%([\w.\-]+)", line)}
    assert bodies
    pairwise = re.compile(r"(^|,)(16,16|16,64,16)(,|$)")
    for c in _called(comps, bodies):
        for line in comps[c]:
            for dims in _result_dims(line):
                assert not pairwise.search(",".join(map(str, dims))), line
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30
