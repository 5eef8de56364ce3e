"""Subprocess body of test_masked_median.py's mesh test (four virtual
devices, which must be set before JAX initialises): with the backend forced
to Pallas, the Median pull over a 'rep'=4 mesh compiles to the streamed
route, with no call of the masked Median kernel; over a mesh of one device
it compiles to the kernel."""
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import protocol
from repro.launch.mesh import make_protocol_mesh, use_mesh

KERNEL = "/masked_median/"   # the kernel's scope in the op_name metadata


def compiled_pull(devices):
    mesh = make_protocol_mesh(4, devices=devices)
    pcfg = protocol.ProtocolConfig(n_groups=4, f_workers=1, f_servers=0,
                                   q_workers=3, q_servers=4)
    params = {"w": jnp.ones((4, 2, 64, 128)), "table": jnp.ones((4, 256, 64)),
              "norm": jnp.ones((4, 128))}
    with use_mesh(mesh):
        shardings = protocol._named_tree_shardings(params, mesh)
        params = jax.tree.map(jax.device_put, params, shardings)
        masks = jax.device_put(jnp.ones((4, 4), bool),
                               NamedSharding(mesh, P()))

        def pull(p, m):
            with protocol.views_in(jnp.bfloat16):
                return protocol.masked_pull(p, m, pcfg, mesh)

        return mesh, jax.jit(pull).lower(params, masks).compile().as_text()


if __name__ == "__main__":
    assert len(jax.devices()) == 4
    mesh, text = compiled_pull(jax.devices())
    assert mesh.shape["rep"] == 4, mesh.shape
    assert KERNEL not in text, "the kernel route was taken on 4 devices"
    mesh, text = compiled_pull(jax.devices()[:1])
    assert mesh.size == 1
    assert KERNEL in text, "no kernel call on a mesh of one device"
    print("MASKED_MEDIAN_MESH_PASS")
