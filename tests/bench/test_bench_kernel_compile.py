"""The cells' kernel shapes compile for a described v5e, without the
chip (on-chip-measurement guide, section 2): `pallas_ec`'s key-table
kernel at the two buckets the full-block X.509 cells dispatch (since
PR 38 no cell dispatches 4096: a flush of 2,049-4,096 lanes runs as
chunks of 2,048), and its per-lane-key kernel at the 8192 bucket of
`manyclients-10k.catchup`, whose two-block flush holds more keys than
the table.  What the chip's compiler refuses here costs no chip time.
Nothing runs, so this says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every test worker imports
every test file.
"""

import os

import pytest

# (kernel, bucket): solo1's flushes and majority5's lone block in chunks;
# majority5's pair of blocks; manyclients-10k's pair, a key a lane
SHAPES = (("ktab", 2048), ("ktab", 8192), ("per_lane", 8192))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kernel, bucket", SHAPES)
def test_pallas_ec_compiles_for_v5e_at_the_cells_buckets(one_chip, kernel, bucket):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from fabric_tpu.csp.tpu import pallas_ec

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.uint32, sharding=one_chip)

    c = pallas_ec._consts()
    consts = [
        c["solmat"], c["bias"], c["r256"], c["r512"], c["sub_c"],
        c["p_limbs"], c["n_limbs"], c["gx"][:, :, 0], c["gy"][:, :, 0],
    ]
    if kernel == "ktab":      # the key table and an index a lane
        keys = [shape(8, pallas_ec.KEYTAB), shape(8, pallas_ec.KEYTAB), shape(1, bucket)]
        build = pallas_ec._build_call_dedup
    else:                     # qx, qy a lane
        keys = [shape(8, bucket), shape(8, bucket)]
        build = pallas_ec._build_call
    args = keys + [
        shape(8, bucket), shape(8, bucket), shape(8, bucket), shape(2, bucket),
    ] + [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in consts
    ]
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        call = build.__wrapped__(bucket // pallas_ec.BLK, pallas_ec.BLK, False)
        compiled = call.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
